"""What a served model holds (the family's ``serving_params``): each leaf
in the dtype the two serving forwards compute with, cast once at load.

GPT-2 trains on float32 parameters and serves in ``cfg.dtype``; its
forwards round a float32 leaf at every use exactly as ``serving_params``
rounds it once, so the two trees must give the SAME bits, not close
ones.  All on the CPU at the tiny presets: nothing here is a time.
"""

import asyncio
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import reference  # noqa: E402
from ray_tpu.models import gpt2, olmoe  # noqa: E402
from ray_tpu.serve.llm import LLMConfig, LLMEngine  # noqa: E402
from ray_tpu.serve.llm.engine import FINISHED  # noqa: E402

BS = 16
CFG = gpt2.GPT2Config.tiny(dtype=jnp.bfloat16)
# the serve cells' ``checks.logit_margin`` (benchmark/workloads/gpt2-large.serve.*.json)
LOGIT_MARGIN = 0.1


@pytest.fixture(scope="module")
def trained():
    """The tree a trainer holds: float32, as ``init_params`` makes it."""
    return gpt2.init_params(CFG, rng=jax.random.PRNGKey(0))


def _by_path(tree):
    return {jax.tree_util.keystr(path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and a.tobytes() == b.tobytes()


def test_gpt2_serving_params_hold_what_the_forwards_compute_with(trained):
    served = _by_path(gpt2.serving_params(trained, CFG))
    before = _by_path(trained)
    assert served.keys() == before.keys()
    norms = [p for p in served if "['ln_" in p]
    assert len(norms) == 2 * (2 * CFG.n_layer + 1)  # scale and bias of ln_1, ln_2, ln_f
    for path, leaf in served.items():
        assert before[path].dtype == jnp.float32  # the caller's tree is left as it was
        if path in norms:
            assert leaf is before[path]
        else:  # kernels and biases of _dense, wte, wpe
            assert leaf.dtype == jnp.bfloat16, path
            assert _same_bits(leaf, before[path].astype(jnp.bfloat16))
    # a float32 server holds the float32 tree
    cfg32 = gpt2.GPT2Config.tiny(dtype=jnp.float32)
    assert all(leaf.dtype == jnp.float32
               for leaf in jax.tree_util.tree_leaves(gpt2.serving_params(trained, cfg32)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_olmoe_serving_params_are_what_init_params_made(dtype):
    cfg = olmoe.OlmoeConfig.olmoe_tiny(dtype=dtype)
    params = olmoe.init_params(cfg, rng=jax.random.PRNGKey(7))
    served = olmoe.serving_params(params, cfg)
    mine, theirs = jax.tree_util.tree_leaves(served), jax.tree_util.tree_leaves(params)
    assert jax.tree_util.tree_structure(served) == jax.tree_util.tree_structure(params)
    assert all(a is b and a.dtype == dtype for a, b in zip(mine, theirs))


def test_prefill_gives_the_same_bits_on_either_tree(trained):
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, CFG.vocab_size, (2, 32)), jnp.int32)
    last = jnp.asarray([20, 31], jnp.int32)
    fn = jax.jit(lambda p: gpt2.prefill_forward(p, CFG, tokens, last_index=last))
    for got, want in zip(fn(gpt2.serving_params(trained, CFG)), fn(trained)):
        assert got.dtype == jnp.bfloat16 and _same_bits(got, want)


def test_paged_decode_gives_the_same_bits_on_either_tree(trained):
    lens = [37, 0, 100, 16]
    B, pages = len(lens), CFG.max_seq_len // BS
    rng = np.random.default_rng(5)
    slots = (B * pages + 1) * BS
    kp, vp = (jnp.asarray(rng.standard_normal((CFG.n_layer, slots, CFG.d_model)), jnp.bfloat16)
              for _ in range(2))
    tables = jnp.asarray(rng.permutation(np.arange(1, B * pages + 1)).reshape(B, pages), jnp.int32)
    tok = jnp.asarray(rng.integers(0, CFG.vocab_size, B), jnp.int32)
    fn = jax.jit(lambda p: gpt2.decode_forward_paged(
        p, CFG, tok, kp, vp, tables, jnp.asarray(lens, jnp.int32), BS))
    for got, want in zip(fn(gpt2.serving_params(trained, CFG)), fn(trained)):
        assert got.dtype == jnp.bfloat16 and _same_bits(got, want)


async def _generate(eng, prompts, max_tokens):
    async def one(prompt):
        req = await eng.add_request(prompt, max_tokens=max_tokens)
        toks = []
        while (ev := await req.out.get()) is not FINISHED:
            toks.append(ev["token"])
        return toks

    try:
        return await asyncio.gather(*[one(p) for p in prompts])
    finally:
        await eng.stop()


def _engine(model, **kw):
    return LLMEngine(LLMConfig(model=model, dtype="bfloat16", max_batch_size=4,
                               num_blocks=64, block_size=BS, **kw))


@pytest.mark.parametrize("model, module", [("tiny", gpt2), ("olmoe_tiny", olmoe)])
def test_engine_keeps_the_served_tree_alone_and_reports_its_size(model, module):
    eng = _engine(model)
    leaves = _by_path(eng.params)
    cfg = eng.model_cfg
    made = module.init_params(cfg, rng=jax.random.PRNGKey(eng.config.seed))
    want = _by_path(module.serving_params(made, cfg))
    assert leaves.keys() == want.keys()
    assert all(_same_bits(leaves[p], want[p]) for p in leaves)
    # no matrix is held in float32: what is, is a norm's scale or bias
    assert all(leaf.ndim == 1 for leaf in leaves.values() if leaf.dtype == jnp.float32)
    size = sum(leaf.size * leaf.dtype.itemsize for leaf in leaves.values())
    assert eng.stats()["param_bytes"] == size
    if module is gpt2:
        n = gpt2.num_params(made)
        norms = 2 * cfg.d_model * (2 * cfg.n_layer + 1)
        assert size == 2 * (n - norms) + 4 * norms < 4 * n


def test_served_tokens_lie_within_the_margin_of_the_float32_weights():
    """The serve cells hold the returned tokens to the float32 reference
    over ``engine.params``, which is the rounded tree since the engine
    holds nothing else; here the reference reads the float32 tree the
    engine's seed makes, so the rounding of the weights is inside the
    comparison: two prompts of 64, 16 tokens each, as the cells send."""
    eng = _engine("tiny", seed=11)
    rng = np.random.default_rng(11)
    cfg = eng.model_cfg
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, 64)] for _ in range(2)]
    answers = asyncio.run(_generate(eng, prompts, 16))
    assert [len(a) for a in answers] == [16, 16]
    original = gpt2.init_params(cfg, rng=jax.random.PRNGKey(11))
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree_util.tree_leaves(original))
    seqs = np.asarray([p + a for p, a in zip(prompts, answers)], np.int32)
    lg = np.asarray(reference.full_logits(original, jnp.asarray(seqs), cfg.n_layer, cfg.n_head))
    worst = max(float(row[pos].max() - row[pos, seq[pos + 1]])
                for row, seq in zip(lg, seqs) for pos in range(63, len(seq) - 1))
    assert worst <= LOGIT_MARGIN, worst
