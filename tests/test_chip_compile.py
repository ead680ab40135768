"""Compiles for a described v5e chip, without the chip.

The TPU's compiler is installed where the tests run, and compiles for a
device that is described and not attached: what it refuses here (a slice
off the tiling, too much fast memory, a program over HBM) it would
refuse on the chip.  These guard the kernels and steps of the main path
at real widths.  Nothing runs: no result or time comes from here.

All in this one file, compiled in the test's own process, topology
described inside a fixture: only one process may load the TPU library,
and the xdist worker that is given this file is the one that does.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the library from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


# GPT-2-medium's attention shape in chip_smoke.py's train phase
FLASH_SHAPE = (8, 1024, 16, 64)


def _qkv(sharding, shape=FLASH_SHAPE):
    return [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)] * 3


def test_flash_forward_compiles_for_v5e(one_chip):
    from ray_tpu.ops.pallas_attention import flash_attention

    compiled = jax.jit(flash_attention).lower(*_qkv(one_chip)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_flash_forward_backward_compiles_for_v5e(one_chip):
    from ray_tpu.ops.pallas_attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*_qkv(one_chip)).compile()
    # a head of 1024 positions is resident: the forward, and one backward
    # kernel for dq, dk and dv
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("shape, kernels", [
    # gpt2-large.train.mesh2x2's shard on a device: 4 of 8 sequences, 10 of 20 heads
    ((4, 1024, 10, 64), 2),
    # llama's head size, a sequence of eight blocks: K/V streamed past Q
    # (forward, dq), Q/dO past K/V (dk/dv), strips of [256, 1024] scores
    ((1, 8192, 8, 128), 3),
    # a sequence that no power of two over 256 divides: one block of three chunks
    ((2, 768, 4, 64), 2),
])
def test_flash_forward_backward_compiles_at_other_shapes(one_chip, shape, kernels):
    """Mosaic refusing a strip's slice, a transpose or the VMEM a step
    needs shows here: `flash_tiles` answers for every (T, D) that
    `_use_pallas` admits, not only for the cells'."""
    from ray_tpu.ops.pallas_attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*_qkv(one_chip, shape)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == kernels


def test_flash_under_a_mesh_compiles_for_v5e(topo):
    """Under a batch x model mesh (the GSPMD plane's layout) the kernel
    runs per shard of B and H inside a shard_map: a Mosaic call that
    reaches a multi-device jit bare fails to lower."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.ops.attention import _flash_over_mesh

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("batch", "model"))
    sharding = NamedSharding(mesh, P("batch", None, "model", None))

    def loss(q, k, v):
        return _flash_over_mesh(q, k, v).astype(jnp.float32).sum()

    with jax.set_mesh(mesh):
        compiled = jax.jit(
            jax.grad(loss, argnums=(0, 1, 2)), out_shardings=(sharding,) * 3
        ).lower(*_qkv(sharding)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # each device works on its own [4, 1024, 8, 64] shard: q/k/v never gather
    assert "all-gather" not in text


def _mesh_step_cfg():
    """GPT-2-large at its published widths, depth cut to two layers."""
    import dataclasses

    from ray_tpu.models import gpt2

    return dataclasses.replace(gpt2.GPT2Config.large(remat=False), n_layer=2)


@pytest.fixture(scope="module")
def mesh_step_text(topo):
    """`gpt2-large.train.mesh2x2`'s step (the sharding plan's jit of
    `make_train_step` under the default rules, 8 x 1024 tokens, mesh
    {batch: 2, model: 2}) at the published widths, depth cut to two
    layers, compiled for the described chips: its optimized HLO."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.models import gpt2
    from ray_tpu.train.sharding import ShardingConfig
    from ray_tpu.train.sharding.gspmd import GspmdPlan
    from ray_tpu.train.sharding.rules import match_partition_rules

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("batch", "model"))
    plan = GspmdPlan(ShardingConfig(mesh_shape={"batch": 2, "model": 2}), mesh)
    cfg = _mesh_step_cfg()
    opt = gpt2.make_adamw()

    def on_mesh(tree, specs):
        return jax.tree_util.tree_map(
            lambda s, x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
            specs, tree, is_leaf=lambda s: isinstance(s, P))

    params = jax.eval_shape(lambda: gpt2.init_params(cfg))
    params = on_mesh(params, plan.param_specs(params))
    opt_state = jax.eval_shape(opt.init, params)
    opt_state = on_mesh(opt_state, match_partition_rules(plan.config.rules(), opt_state, mesh, strict=False))
    tokens = jax.ShapeDtypeStruct((8, 1024), jnp.int32, sharding=plan.data_sharding())
    step = plan.jit_train_step(gpt2.make_train_step(cfg, opt), params, opt_state)
    return step.lower(params, opt_state, tokens, tokens).compile().as_text()


def test_mesh_train_step_moves_weights_not_activations(mesh_step_text):
    """What crosses `model` in the mesh step beside Megatron's sums is
    the fused projection's weights, once forward and once backward a
    layer.  q, k and v leave the projection on their device's heads, so
    nothing of a sequence's length is permuted, exchanged or gathered
    around the flash kernel's shard_map."""
    from ray_tpu.parallel.collectives import collectives, format_collectives

    cfg = _mesh_step_cfg()
    B, T, d = 8, 1024, cfg.d_model
    text = mesh_step_text
    rows = collectives(text)
    listing = format_collectives(rows)

    def count(op, where=lambda r: True):
        return sum(r.count for r in rows if r.op == op and where(r))

    assert count("collective-permute") == 0, listing
    # nothing T long is exchanged or gathered: the activations stay where they are
    assert count("all-to-all", lambda r: T in r.dims()) == 0, listing
    assert count("all-gather", lambda r: T in r.dims()) == 0, listing
    # a device's half of the weights [d, 3d] in bf16: forward, and the gradient back
    assert count("all-to-all") == 2 * cfg.n_layer, listing
    assert all(r.shape.startswith("bf16[") and math.prod(r.dims()) == d * 3 * d // 2
               for r in rows if r.op == "all-to-all"), listing
    # Megatron's four sums a layer and the head's two, one of them in a tuple with the loss's
    local = f"bf16[{B // 2},{T},{d}]"
    assert count("all-reduce", lambda r: r.shape == local) == 4 * cfg.n_layer + 1, listing
    assert count("all-reduce", lambda r: r.shape.startswith(local + "+")) == 1, listing
    # `_qkv_by_head`'s layout pins hold: left to itself the compiler
    # lays the exchanged blocks out rows-minor and transposes the
    # float32 kernel, its moments and its gradient to match (six copies
    # of a device's 9.8 MB a layer, 144+ MB of float32 copied a layer
    # for the pinned form's 50 and the column-sharded step's 65)
    def f32_copied(op):  # elements of each float32 result of `op`
        return [math.prod(int(n) for n in dims.split(","))
                for dims in re.findall(rf" = \(?f32\[([\d,]+)\][^=]*? {op}\(", text)]

    assert d * 3 * d // 2 not in f32_copied("copy"), f32_copied("copy")
    assert 4 * sum(f32_copied("copy(?:-start)?")) < 65e6 * cfg.n_layer


def test_mesh_train_step_starts_backward_sums_async(mesh_step_text):
    """The plan compiles a step for several TPUs with the options that
    let an all-reduce start asynchronously: the backward pass's sums of
    dx over `model` (`bf16[4,1024,1280]`) each start in an
    `async-collective-start` fusion and end in its `-done`, with a
    weight-gradient matmul of the same layer between the two (the
    compiler carries the sum through that fusion).  Nothing is claimed
    of the forward pass's sums: what one of them can run beside is the
    compiler's to find."""
    from ray_tpu.parallel.collectives import collectives, format_collectives

    text = mesh_step_text
    rows = collectives(text)
    listing = format_collectives(rows)
    over_model = ("[2,2]<=[4]", "{{0,1},{2,3}}")
    sums = [r for r in rows if r.op == "all-reduce" and r.shape == "bf16[4,1024,1280]"]
    assert sums and all(r.groups in over_model for r in sums), listing
    assert sum(r.started_async for r in sums) >= 3, listing

    computations = dict(re.findall(r"^%(\S+) \(.*?\{$(.*?)^\}$", text, flags=re.M | re.S))
    entry = text[text.index("\nENTRY"):].splitlines()
    at = {m[1]: i for i, ln in enumerate(entry) if (m := re.match(r"\s*%(async-collective-\S+) = ", ln))}
    backward = 0
    for name, first in at.items():
        if not name.startswith("async-collective-start"):
            continue
        last = at[name.replace("start", "done")]
        assert first < last
        started = computations[re.search(r"calls=%([^\s,)]+)", entry[first])[1]]
        summed = started.split(" all-reduce(")[0].rsplit("\n", 1)[-1]  # the sum's name and result
        if " = bf16[4,1024,1280]" not in summed or "transpose(jvp(GPT2))" not in started:
            continue  # another collective than a sum of dx, or one of the forward pass
        backward += 1
        # a matmul that yields a weight's gradient (no [.., T, ..] activation) runs under it
        beside = [ln for ln in entry[first + 1:last]
                  if "calls=%async_collective_fusion" in ln and "dot_general" in ln and "transpose(jvp(GPT2))" in ln]
        results = [re.match(r"\s*%\S+ = \(?(\w+\[[\d,]+\])", ln)[1] for ln in beside]
        assert any(",1024," not in r for r in results), (name, results, listing)
    assert backward >= 3, listing


def test_engine_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """The serving engine's decode step (attention over the paged pool
    read in place -> one token -> the new K/V written back) at
    GPT-2-small width, depth cut to two layers so that the compile takes
    seconds; chip_smoke.py's rehearsal covers medium."""
    import dataclasses

    from ray_tpu.models import gpt2
    from ray_tpu.serve.llm.engine import decode_step

    # the dispatch asks for the backend and sees the CPU here: steer it
    # to the path the chip takes
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(gpt2.GPT2Config.small(dtype=jnp.bfloat16), n_layer=2)
    B, C, block = 8, cfg.max_seq_len, 16
    slots = 8 * 1024 + block

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree
        )

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = shaped(jax.eval_shape(lambda: gpt2.init_params(cfg)))
    pages = arr((cfg.n_layer, slots, cfg.d_model), cfg.dtype)
    key = shaped(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    compiled = jax.jit(
        lambda *a: decode_step(cfg, 0, block, gpt2.cache_spec(cfg, block), *a), donate_argnums=(1, 2)
    ).lower(
        params, pages, pages, arr((B,), jnp.int32), arr((B,), jnp.int32),
        arr((B, C // block), jnp.int32), arr((B,), jnp.int32),
        arr((B,), jnp.float32), key,
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 2**30
    # attention is the paged kernel, one call a layer
    assert compiled.as_text().count("tpu_custom_call") == cfg.n_layer
    # and nothing of B x max_ctx positions is built.  One layer's keys
    # alone at that size are 8 * 1024 positions * 768 * 2 B = 12.6 MB;
    # the gathered K and V of two layers were 50 MB of the 53.4 MB of
    # temporaries this program had before it read pages in place; what
    # is left is 3.3 MB of activations and casts.
    assert mem.temp_size_in_bytes < B * C * cfg.d_model * 2


def test_olmoe_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """The same decode step on the OLMoE family at its published widths
    (d 2048, 16 heads of 128, 64 experts of width 1024, 8 a token,
    vocabulary 50,304), 32 lanes over 4096 positions, depth cut to two
    layers: the paged kernel takes a 2048-wide row, the experts are the
    grouped-matmul kernel, and the routing builds nothing of
    [pairs, experts] size."""
    from ray_tpu.models import olmoe
    from ray_tpu.serve.llm.engine import decode_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = olmoe.OlmoeConfig.olmoe_1b_7b(n_layer=2)
    B, C, block = 32, cfg.max_seq_len, 16
    slots = 32 * 1024 + block

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree
        )

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = shaped(jax.eval_shape(lambda: olmoe.init_params(cfg)))
    pages = arr((cfg.n_layer, slots, cfg.d_model), cfg.dtype)
    key = shaped(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    compiled = jax.jit(
        lambda *a: decode_step(cfg, 0, block, olmoe.cache_spec(cfg, block), *a), donate_argnums=(1, 2)
    ).lower(
        params, pages, pages, arr((B,), jnp.int32), arr((B,), jnp.int32),
        arr((B, C // block), jnp.int32), arr((B,), jnp.int32),
        arr((B,), jnp.float32), key,
    ).compile()
    text = compiled.as_text()
    calls = [ln.split(" = ")[0].split("%")[-1] for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    # a layer: one paged-attention call, and two grouped matmuls (gate
    # and up side by side, then down)
    assert sum(c.startswith("paged_decode_attention") for c in calls) == cfg.n_layer
    assert sum(c.startswith("moe_gmm") for c in calls) == 2 * cfg.n_layer
    assert len(calls) == 3 * cfg.n_layer
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 2**30
    # 256 pairs over 64 experts: a [pairs, experts, d] dispatch tensor
    # would be 256 * 64 * 2048 * 2 B = 67 MB; the whole program's
    # temporaries (rows in and out of the experts, logits) stay under a
    # quarter of that
    pairs = B * cfg.num_experts_per_tok
    assert mem.temp_size_in_bytes < pairs * cfg.num_experts * cfg.d_model * 2 // 4
    # the program returns its tokens and five counters in one array
    assert f"s32[{B + len(olmoe.COUNTERS)}]" in text


def test_minicpm_sala_programs_compile_for_v5e(one_chip, monkeypatch):
    """MiniCPM-SALA's two programs at the published widths (d 4096, 32
    query heads of 128 over 2 K/V heads, lightning 32 x 128, feed-forward
    16,384), 16 lanes over 33,792 positions in pages of 16, depth cut to
    one layer of each kind: the decode step reads the chosen pages through
    the sparse paged kernel (one call a sparse layer) and builds nothing
    of [lanes, max_ctx] keys; a 1,024-token chunk of a prompt compiles
    with its loop over key blocks and fits beside the cache."""
    from ray_tpu.models import minicpm_sala as sala
    from ray_tpu.serve.llm.engine import decode_step, prefill_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = sala.MiniCPMSalaConfig(mixer_types=(sala.LIGHTNING, sala.SPARSE))
    B, C, block, T = 16, 33792, 16, 1024
    blocks_in_pool = 4 * C // block + 1
    spec = sala.cache_spec(cfg, block)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def shaped(tree):
        return jax.tree_util.tree_map(lambda x: arr(x.shape, x.dtype), tree)

    params = shaped(jax.eval_shape(lambda: sala.init_params(cfg)))
    key = shaped(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    pool = arr((spec.paged_layers, blocks_in_pool * block, spec.row_width), cfg.dtype)
    cache = [pool, pool]
    cache += [arr((spec.paged_layers, blocks_in_pool * rows, width), dtype)
              for _, rows, width, dtype in spec.page_extras]
    cache += [arr((B, *shape), dtype) for _, shape, dtype in spec.lane_state]
    assert spec.names == ("k_pages", "v_pages", "ck_pages", "lightning_state_0")
    held = tuple(range(1, 1 + len(cache)))

    decode = jax.jit(lambda *a: decode_step(cfg, 0, block, spec, *a), donate_argnums=held).lower(
        params, *cache, arr((B,), jnp.int32), arr((B,), jnp.int32), arr((B, C // block), jnp.int32),
        arr((B,), jnp.int32), arr((B,), jnp.float32), key).compile()
    text = decode.as_text()
    calls = [ln.split(" = ")[0].split("%")[-1] for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1 and calls[0].startswith("sparse_paged_decode_attention")
    # the keys of 16 lanes at 33,792 positions would be 16 * 33792 * 256 * 2 B = 277 MB a
    # layer; what is gathered is a compressed key every 16 positions (17 MB) and the logits
    assert decode.memory_analysis().temp_size_in_bytes < B * C * spec.row_width * 2 // 2
    assert f"s32[{B + len(sala.COUNTERS)}]" in text

    chunk = jax.jit(lambda *a: prefill_step(cfg, 0, block, spec, *a), donate_argnums=held).lower(
        params, *cache, arr((1, T), jnp.int32), arr((T,), jnp.int32), arr((1,), jnp.int32),
        arr((1,), jnp.float32), key, arr((), jnp.int32), arr((C // block,), jnp.int32),
        arr((), jnp.int32)).compile()
    mem = chunk.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 2**30
    # the scores of 1,024 queries x 32 heads over 33,792 keys would be 4.4 GB in float32
    assert mem.temp_size_in_bytes < 1 * 2**30


def test_mistral_small_4_programs_compile_for_v5e(one_chip, monkeypatch):
    """Mistral-Small-4's two programs at the published widths (d 4096,
    32 heads over one latent row of 320 stored as 384, q rank 1024, 32
    held experts of width 2048 of 128 routed, a shared one), 48 lanes
    over 36,864 positions in pages of 64, depth cut to one layer: the
    decode step reads the latent pages through the absorbed kernel (one
    call a layer, two grouped matmuls) and builds nothing of [lanes,
    context, heads, 192]; a 4,096-token chunk compiles with its loop over
    key blocks and fits beside the weights and the cache."""
    from ray_tpu.models import mistral4
    from ray_tpu.serve.llm.engine import decode_step, prefill_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = mistral4.Mistral4Config.mistral_small_4_6l_ep4(n_layer=1)
    B, C, block, T = 48, 36864, 64, 4096
    spec = mistral4.cache_spec(cfg, block)
    assert spec.names == ("k_pages",) and spec.row_width == 384

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def shaped(tree):
        return jax.tree_util.tree_map(lambda x: arr(x.shape, x.dtype), tree)

    params = shaped(jax.eval_shape(lambda: mistral4.init_params(cfg)))
    key = shaped(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    pool = arr((spec.paged_layers, 786432 + block, spec.row_width), cfg.dtype)

    decode = jax.jit(lambda *a: decode_step(cfg, 0, block, spec, *a), donate_argnums=(1,)).lower(
        params, pool, arr((B,), jnp.int32), arr((B,), jnp.int32), arr((B, C // block), jnp.int32),
        arr((B,), jnp.int32), arr((B,), jnp.float32), key).compile()
    text = decode.as_text()
    calls = [ln.split(" = ")[0].split("%")[-1] for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert sum(c.startswith("mla_paged_decode_attention") for c in calls) == 1
    assert sum(c.startswith("moe_gmm") for c in calls) == 2 and len(calls) == 3
    # keys and values of 48 lanes x 36,864 positions expanded to 32 heads of 192 would be 21.7 GB a
    # layer, one lane's 453 MB; the step's temporaries are rows of the experts and the logits
    assert decode.memory_analysis().temp_size_in_bytes < 32 * 2**20
    assert f"s32[{B + len(mistral4.COUNTERS)}]" in text
    # the pool keeps its row-major layout through the step: no copy of it
    assert "bf16[1,786496,384]{1,2,0" not in text

    chunk = jax.jit(lambda *a: prefill_step(cfg, 0, block, spec, *a), donate_argnums=(1,)).lower(
        params, pool, arr((1, T), jnp.int32), arr((T,), jnp.int32), arr((1,), jnp.int32),
        arr((1,), jnp.float32), key, arr((), jnp.int32), arr((C // block,), jnp.int32),
        arr((), jnp.int32)).compile()
    mem = chunk.memory_analysis()
    # the scores of 4,096 queries x 32 heads over 36,864 keys would be 19 GB in float32
    assert mem.temp_size_in_bytes < 1 * 2**30
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 2**30
    # the chunk attends in the kernel, one call a layer: no loop's scores of a tile over a key block,
    # and no more room than the loop took (the cell stands at 15.69 of 16 GB)
    text = chunk.as_text()
    assert [c.split(".")[0] for c in _kernel_calls(text)].count("mla_chunk_attention") == cfg.n_layer
    assert "f32[32,1024,512]" not in text
    assert mem.temp_size_in_bytes <= 410_944_000, "the parent's chunk program (3f1f045, the XLA loop): 410,944,000"


def test_glm_moe_dsa_chunk_keeps_its_loop_for_v5e(one_chip, monkeypatch):
    """GLM-5's chunk program (1,024 tokens, the dense layer and one
    expert layer) attends under the index's choice: a mask a tile, so
    ``expanded_attention``'s loop over key blocks and no chunk kernel."""
    from ray_tpu.models import glm_moe_dsa
    from ray_tpu.serve.llm.engine import prefill_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = glm_moe_dsa.GlmMoeDsaConfig.glm5_6l_ep16(n_layer=2)
    C, block, slots, T = 36864, 64, 458752 + 64, 1024
    spec = glm_moe_dsa.cache_spec(cfg, block)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def shaped(tree):
        return jax.tree_util.tree_map(lambda x: arr(x.shape, x.dtype), tree)

    params = shaped(jax.eval_shape(lambda: glm_moe_dsa.init_params(cfg)))
    key = shaped(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    cache = [arr((2, slots, 640), cfg.dtype), arr((2, slots, 128), cfg.dtype)]
    text = jax.jit(lambda *a: prefill_step(cfg, 0, block, spec, *a), donate_argnums=(1, 2)).lower(
        params, *cache, arr((1, T), jnp.int32), arr((T,), jnp.int32), arr((1,), jnp.int32),
        arr((1,), jnp.float32), key, arr((), jnp.int32), arr((C // block,), jnp.int32),
        arr((), jnp.int32)).compile().as_text()
    assert not any(c.startswith("mla_chunk_attention") for c in _kernel_calls(text))
    # a tile of 512 queries a layer runs the attention's loop (the choice's passes run more)
    assert len(re.findall(r" while\(", text)) >= 2 * (T // 512)
    assert "while/body/mla.attend" in text


@pytest.mark.parametrize("lanes, H, G", [(128, 64, 8), (32, 128, 1)], ids=["nemotron-h", "granite-4.0-h"])
def test_mamba2_decode_step_compiles_for_v5e(one_chip, lanes, H, G):
    """The Mamba-2 decode kernel alone at the two served shapes (heads of
    64 x 128 float32; 128 lanes of 64 heads in 8 groups, 32 lanes of 128
    heads in ONE group: 4 MB a lane, two in and two out of them 16 MB of
    the kernel's VMEM): one custom call, which takes the float32
    ``HIGHEST`` contraction of two heads' new states with C and fits its
    ``vmem_limit_bytes`` (the compiler would refuse it here), the states
    going out in the buffer they came in, nothing of a state's size
    among the temporaries."""
    from ray_tpu.ops import pallas_mamba2

    P, N = 64, 128
    assert pallas_mamba2.kernel_takes(H, P, N, G)

    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(pallas_mamba2.mamba2_decode_step, donate_argnums=(6,)).lower(
        arr((lanes, H, P), jnp.bfloat16), arr((lanes, H)), arr((H,)), arr((lanes, G, N)), arr((lanes, G, N)),
        arr((H,)), arr((lanes, H, P, N)), arr((lanes,), jnp.bool_)).compile()
    text = compiled.as_text()
    calls = [ln.split(" = ")[0].split("%")[-1] for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1 and calls[0].startswith("mamba2_decode_step")
    lane_bytes = H * P * N * 4
    assert 4 * lane_bytes < pallas_mamba2._VMEM_BYTES
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == lanes * lane_bytes
    assert mem.temp_size_in_bytes < lane_bytes


def test_nemotron_h_programs_compile_for_v5e(one_chip, monkeypatch):
    """Nemotron-H's two programs at the published widths (d 2688; Mamba-2
    64 heads of 64, state 128, 8 groups, convolution 4; 32 query heads of
    128 over 2 K/V heads; 32 held experts of width 1,856 of 128 routed, a
    shared one of 3,712), 128 lanes over 6,144 positions in pages of 64,
    depth cut to one layer of each kind (M, E, *): the decode step updates
    the lanes' states through the Mamba-2 kernel INTO the donated array
    (no copy of a state array, of a tail or of a pool anywhere in the
    program), reads the K/V pages through the grouped-query kernel and
    runs two grouped matmuls; a 2,048-token chunk compiles with its
    chunked scan and its loop over key blocks and fits beside the cache."""
    from ray_tpu.models import nemotron_h as nh
    from ray_tpu.serve.llm.engine import decode_step, prefill_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = nh.NemotronHConfig.nemotron_3_nano_26l_ep4(pattern="ME*")
    B, C, block, T, slots = 128, 6144, 64, 2048, 393216 + 64
    spec = nh.cache_spec(cfg, block)
    assert spec.names == ("k_pages", "v_pages", "conv_tail_0", "ssm_state_0")

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def shaped(tree):
        return jax.tree_util.tree_map(lambda x: arr(x.shape, x.dtype), tree)

    params = shaped(jax.eval_shape(lambda: nh.init_params(cfg)))
    key = shaped(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    pool = arr((spec.paged_layers, slots, spec.row_width), cfg.dtype)
    cache = [pool, pool] + [arr((B, *shape), dtype) for _, shape, dtype in spec.lane_state]
    held = tuple(range(1, 1 + len(cache)))
    state_bytes = B * 64 * 64 * 128 * 4
    held_arrays = ("f32[128,64,64,128]", "bf16[128,18432]", f"bf16[1,{slots},256]")

    def copies(text):
        return [ln.strip()[:120] for ln in text.splitlines()
                if " copy(" in ln and any(a in ln.split(" copy(")[0] for a in held_arrays)]

    decode = jax.jit(lambda *a: decode_step(cfg, 0, block, spec, *a), donate_argnums=held).lower(
        params, *cache, arr((B,), jnp.int32), arr((B,), jnp.int32), arr((B, C // block), jnp.int32),
        arr((B,), jnp.int32), arr((B,), jnp.float32), key).compile()
    text = decode.as_text()
    calls = [ln.split(" = ")[0].split("%")[-1] for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert sum(c.startswith("mamba2_decode_step") for c in calls) == 1
    assert sum(c.startswith("gqa_paged_decode_attention") for c in calls) == 1
    assert sum(c.startswith("moe_gmm") for c in calls) == 2 and len(calls) == 4
    assert copies(text) == []
    mem = decode.memory_analysis()
    # every held array goes out in the buffer it came in: both pools, the tail, the state
    assert mem.alias_size_in_bytes >= state_bytes + 2 * slots * 256 * 2 + B * 18432 * 2
    assert mem.temp_size_in_bytes < state_bytes  # no second array of states among the temporaries
    assert f"s32[{B + len(nh.COUNTERS)}]" in text

    chunk = jax.jit(lambda *a: prefill_step(cfg, 0, block, spec, *a), donate_argnums=held).lower(
        params, *cache, arr((1, T), jnp.int32), arr((T,), jnp.int32), arr((1,), jnp.int32),
        arr((1,), jnp.float32), key, arr((), jnp.int32), arr((C // block,), jnp.int32),
        arr((), jnp.int32)).compile()
    assert copies(chunk.as_text()) == []
    mem = chunk.memory_analysis()
    # the scores of 2,048 queries x 32 heads over 8,192 keys would be 2.1 GB in float32
    assert mem.temp_size_in_bytes < 1 * 2**30
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 2**30


def test_granite_hybrid_programs_compile_for_v5e(one_chip, monkeypatch):
    """Granite 4.0-H's two programs at the published widths (d 4096;
    Mamba-2 128 heads of 64, state 128, ONE group, scan blocks of 256; 32
    query heads of 128 over 8 K/V heads, FOUR a group; 36 held SwiGLU
    experts of width 768 of 72 routed, a shared one of 1,536; the tied
    head over 50,176 rows), 32 lanes over 17,408 positions in pages of
    64, depth cut to one layer of each kind: the decode step updates a
    lane's 4 MB state through the Mamba-2 kernel INTO the donated array,
    reads the K/V pages through the grouped-query kernel (a group of 4
    padded to a sublane tile: no gather of a ``[lanes, max_ctx, ...]``
    context) and runs two grouped matmuls a layer; a 2,048-token chunk
    compiles with its scan in blocks of 256 and fits beside the cache."""
    from ray_tpu.models import granite_hybrid as gh
    from ray_tpu.serve.llm.engine import decode_step, prefill_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = gh.GraniteHybridConfig.granite_4_0_h_small_10l_ep2(layer_types=("mamba", "attention"))
    B, C, block, T, slots = 32, 17408, 64, 2048, 557056 + 64
    spec = gh.cache_spec(cfg, block)
    assert spec.names == ("k_pages", "v_pages", "conv_tail_0", "ssm_state_0")

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def shaped(tree):
        return jax.tree_util.tree_map(lambda x: arr(x.shape, x.dtype), tree)

    params = shaped(jax.eval_shape(lambda: gh.init_params(cfg)))
    key = shaped(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    pool = arr((spec.paged_layers, slots, spec.row_width), cfg.dtype)
    cache = [pool, pool] + [arr((B, *shape), dtype) for _, shape, dtype in spec.lane_state]
    held = tuple(range(1, 1 + len(cache)))
    state_bytes = B * 128 * 64 * 128 * 4

    decode = jax.jit(lambda *a: decode_step(cfg, 0, block, spec, *a), donate_argnums=held).lower(
        params, *cache, arr((B,), jnp.int32), arr((B,), jnp.int32), arr((B, C // block), jnp.int32),
        arr((B,), jnp.int32), arr((B,), jnp.float32), key).compile()
    text = decode.as_text()
    calls = [ln.split(" = ")[0].split("%")[-1] for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert sum(c.startswith("mamba2_decode_step") for c in calls) == 1
    assert sum(c.startswith("gqa_paged_decode_attention") for c in calls) == 1
    assert sum(c.startswith("moe_gmm") for c in calls) == 4 and len(calls) == 6
    # the gather path would build K and V contexts of 32 x 17,408 positions: nothing of that extent is here
    assert f"[{B},{C}," not in text
    mem = decode.memory_analysis()
    # every held array goes out in the buffer it came in: both pools, the tail, the state
    assert mem.alias_size_in_bytes >= state_bytes + 2 * slots * 1024 * 2 + B * 25344 * 2
    assert mem.temp_size_in_bytes < state_bytes  # no second array of states among the temporaries
    assert f"s32[{B + len(gh.COUNTERS)}]" in text

    chunk = jax.jit(lambda *a: prefill_step(cfg, 0, block, spec, *a), donate_argnums=held).lower(
        params, *cache, arr((1, T), jnp.int32), arr((T,), jnp.int32), arr((1,), jnp.int32),
        arr((1,), jnp.float32), key, arr((), jnp.int32), arr((C // block,), jnp.int32),
        arr((), jnp.int32)).compile()
    mem = chunk.memory_analysis()
    # a block's [256, 256, 128] decays in float32 are 33.5 MB, three of them live; the whole stays under 2 GB
    assert mem.temp_size_in_bytes < 2 * 2**30
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 2**30


def _kernel_calls(text):
    """The names of a compiled program's Pallas calls."""
    return [ln.split(" = ")[0].split("%")[-1] for ln in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]


def test_mamba1_kernels_compile_for_v5e(one_chip):
    """The two Mamba-1 kernels alone at Jamba2-3B's shapes (a state of 16
    x 5,120 float32 a lane a layer): the decode kernel over 256 lanes in
    blocks of eight (2.6 MB of states in and 2.6 MB out a grid step, twice
    for the double buffering, inside its ``vmem_limit_bytes``: the
    compiler would refuse it here), the states going out in the buffer
    they came in; the chunk kernel on 2,048 and on 32 positions, with
    nothing of ``[T, 5120, 16]`` (671 MB at 2,048) among its temporaries:
    what it holds beside its arguments are the float32 rows XLA hands it
    (``dt``, ``dt x``, ``y``: 42 MB each)."""
    from ray_tpu.ops import pallas_mamba1

    lanes, N, D = 256, 16, 5120
    assert pallas_mamba1.step_kernel_takes(lanes, N, D)

    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    step = jax.jit(pallas_mamba1.mamba1_decode_step, donate_argnums=(6,)).lower(
        arr((lanes, D), jnp.bfloat16), arr((lanes, D)), arr((N, D)), arr((lanes, N)), arr((lanes, N)), arr((D,)),
        arr((lanes, N, D)), arr((lanes,), jnp.bool_)).compile()
    calls = _kernel_calls(step.as_text())
    assert len(calls) == 1 and calls[0].startswith("mamba1_decode_step")
    state_bytes = lanes * N * D * 4
    assert 4 * pallas_mamba1._LANES_A_BLOCK * N * D * 4 < pallas_mamba1._VMEM_BYTES
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes == state_bytes
    assert mem.temp_size_in_bytes < state_bytes // 4  # the rows, never a second array of states
    for T in (2048, 32):
        assert pallas_mamba1.chunk_kernel_takes(T, N, D)
        chunk = jax.jit(pallas_mamba1.mamba1_chunk_scan).lower(
            arr((T, D), jnp.bfloat16), arr((T, D)), arr((N, D)), arr((T, N)), arr((T, N)), arr((D,)), arr((N, D)),
            arr((), jnp.int32)).compile()
        calls = _kernel_calls(chunk.as_text())
        assert len(calls) == 1 and calls[0].startswith("mamba1_chunk_scan")
        assert chunk.memory_analysis().temp_size_in_bytes < 4 * T * D * 4  # far under T x 5120 x 16 x 4


def test_jamba_programs_compile_for_v5e(one_chip, monkeypatch):
    """Jamba2-3B's two programs at the published widths (d 2560; Mamba-1
    of 5,120 channels x 16 state values, ``dt`` through rank 160; 20 query
    heads of 128 on ONE K/V head; the SwiGLU of 8,192; the tied head over
    65,536 rows), 256 lanes over 8,192 positions in pages of 64, depth cut
    to one layer of each kind: the decode step updates the lanes' states
    through the Mamba-1 kernel INTO the donated array and reads the K/V
    pages through the grouped-query kernel (a group of 20 padded to 32
    rows: no gather of a ``[lanes, max_ctx, ...]`` context); a
    2,048-token chunk runs its scan in the chunk kernel (no ``while`` a
    position, nothing of ``[2048, 5120, 16]``) and fits beside the
    cache."""
    from ray_tpu.models import jamba
    from ray_tpu.serve.llm.engine import decode_step, prefill_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = jamba.JambaConfig.jamba2_3b(n_layer=2, attn_layer_period=2, attn_layer_offset=1)
    assert cfg.layer_types == ("mamba", "attention")
    B, C, block, T, slots = 256, 8192, 64, 2048, 786432 + 64
    spec = jamba.cache_spec(cfg, block)
    assert spec.names == ("k_pages", "v_pages", "conv_tail_0", "ssm_state_0")

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def shaped(tree):
        return jax.tree_util.tree_map(lambda x: arr(x.shape, x.dtype), tree)

    params = shaped(jax.eval_shape(lambda: jamba.init_params(cfg)))
    key = shaped(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    pool = arr((spec.paged_layers, slots, spec.row_width), cfg.dtype)
    cache = [pool, pool] + [arr((B, *shape), dtype) for _, shape, dtype in spec.lane_state]
    held = tuple(range(1, 1 + len(cache)))
    state_bytes = B * 16 * 5120 * 4

    decode = jax.jit(lambda *a: decode_step(cfg, 0, block, spec, *a), donate_argnums=held).lower(
        params, *cache, arr((B,), jnp.int32), arr((B,), jnp.int32), arr((B, C // block), jnp.int32),
        arr((B,), jnp.int32), arr((B,), jnp.float32), key).compile()
    text = decode.as_text()
    calls = _kernel_calls(text)
    assert sum(c.startswith("mamba1_decode_step") for c in calls) == 1
    assert sum(c.startswith("gqa_paged_decode_attention") for c in calls) == 1 and len(calls) == 2
    assert f"[{B},{C}," not in text  # the gather path's contexts
    mem = decode.memory_analysis()
    # every held array goes out in the buffer it came in: both pools, the tail, the state
    assert mem.alias_size_in_bytes >= state_bytes + 2 * slots * 128 * 2 + B * 15360 * 2
    assert mem.temp_size_in_bytes < state_bytes  # no second array of states among the temporaries
    assert f"s32[{B + len(jamba.COUNTERS)}]" in text

    chunk = jax.jit(lambda *a: prefill_step(cfg, 0, block, spec, *a), donate_argnums=held).lower(
        params, *cache, arr((1, T), jnp.int32), arr((T,), jnp.int32), arr((1,), jnp.int32),
        arr((1,), jnp.float32), key, arr((), jnp.int32), arr((C // block,), jnp.int32),
        arr((), jnp.int32)).compile()
    text = chunk.as_text()
    assert [c.split(".")[0] for c in _kernel_calls(text)] == ["mamba1_chunk_scan"]
    assert "f32[2048,5120,16]" not in text and "f32[2048,16,5120]" not in text
    mem = chunk.memory_analysis()
    assert mem.temp_size_in_bytes < 2**29  # 671 MB would be ONE layer's states a position
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 2**30


def test_moe_combine_moves_the_pairs_rows_once(one_chip, monkeypatch):
    """`moe_experts` at Granite 4.0-H's chunk shape (2,048 tokens x 10
    experts a token = 20,480 pairs of 4,096 columns, 36 held SwiGLU
    experts of width 768 of 72): the second grouped matmul writes the
    pairs' rows as 168 MB of bf16, and the combine gathers them ONCE, in
    bf16, and widens, weighs and sums them in one fused pass.  Before
    PR 43 it wrote them out three times in float32 (a product, a gather,
    a `[2048, 10, 4096]` copy whose 10 rows a token are padded to 16),
    2.8 GB a layer as laid out: no float32 tensor of the pairs' size may be produced
    more than once, none with k as the second-minor dim may exist, and
    the temporaries (873 MB then) stay under 560 MB."""
    from ray_tpu.ops import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    T, k, d, f, E = 2048, 10, 4096, 768, 36

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(lambda *a: moe.moe_experts(*a, held=(0, E))).lower(
        arr((T, d), jnp.bfloat16), arr((T, k), jnp.float32), arr((T, k), jnp.int32),
        arr((E, d, 2 * f), jnp.bfloat16), arr((E, f, d), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    # results of the instructions that run, not of what is fused into them
    wide = [m.group(0) for m in re.finditer(r" = f32\[([0-9,]+)\]", entry)
            if math.prod(int(x) for x in m.group(1).split(",")) == T * k * d]
    assert len(wide) <= 1, wide
    assert f"[{T},{k},{d}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 560e6


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill"])
def test_zaya_programs_compile_for_v5e(one_chip, monkeypatch, program):
    """ZAYA1-8B's two programs at the cell's OWN sizes, all 20 layers
    (d 2048; 8 query heads on 2 K/V heads of 128 behind the two
    convolutions; 16 experts of 2,048 and the skip output behind the
    router's three small matmuls; the tied head over 262,272 rows), 48
    lanes over a pool of 229,376 positions in pages of 64, sequences of
    up to 16,384: the decode step reads every layer's pages through the
    grouped-query kernel (four queries a group; no gather of a ``[lanes,
    max_ctx, ...]`` context) and its experts through the grouped matmul;
    a 2,048-token chunk runs the same grouped matmul and no decode
    kernel.  Every held array, the two pools and the twenty tails, goes
    out in the buffer it came in, and the program's temporaries fit
    beside 9.38 GB of weights and 4.70 GB of pool under the engine's
    budget of a 16 GB chip."""
    from ray_tpu.models import zaya
    from ray_tpu.serve.llm.engine import decode_step, prefill_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = zaya.ZayaConfig.zaya1_8b_20l()
    B, C, block, T, slots = 48, 16384, 64, 2048, 229376 + 64
    spec = zaya.cache_spec(cfg, block)
    assert spec.names == ("k_pages", "v_pages", *(f"cca_tail_{i}" for i in range(20)))

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def shaped(tree):
        return jax.tree_util.tree_map(lambda x: arr(x.shape, x.dtype), tree)

    params = shaped(jax.eval_shape(lambda: zaya.init_params(cfg)))
    key = shaped(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    pool = arr((spec.paged_layers, slots, spec.row_width), cfg.dtype)
    cache = [pool, pool] + [arr((B, *shape), dtype) for _, shape, dtype in spec.lane_state]
    held = tuple(range(1, 1 + len(cache)))
    held_bytes = 2 * 20 * slots * 256 * 2 + B * 107_520
    if program == "serve_decode":
        compiled = jax.jit(lambda *a: decode_step(cfg, 0, block, spec, *a), donate_argnums=held).lower(
            params, *cache, arr((B,), jnp.int32), arr((B,), jnp.int32), arr((B, C // block), jnp.int32),
            arr((B,), jnp.int32), arr((B,), jnp.float32), key).compile()
    else:
        compiled = jax.jit(lambda *a: prefill_step(cfg, 0, block, spec, *a), donate_argnums=held).lower(
            params, *cache, arr((1, T), jnp.int32), arr((T,), jnp.int32), arr((1,), jnp.int32),
            arr((1,), jnp.float32), key, arr((), jnp.int32), arr((C // block,), jnp.int32),
            arr((), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [c.split(".")[0] for c in _kernel_calls(text)]
    assert calls.count("moe_gmm") == 2 * 20  # gate and up side by side, then down, a layer
    assert calls.count("gqa_paged_decode_attention") == (20 if program == "serve_decode" else 0)
    assert len(calls) == (60 if program == "serve_decode" else 40)
    assert f"[{B},{C}," not in text  # the gather path's contexts
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= held_bytes
    assert mem.argument_size_in_bytes >= 2 * 4_688_636_304 + held_bytes
    # a decode step's temporaries are its logits (48 x 262,272 float32) and little more; a chunk's under 0.6 GB
    assert mem.temp_size_in_bytes < (80e6 if program == "serve_decode" else 600e6)
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
    if program == "serve_decode":
        assert f"s32[{B + len(zaya.COUNTERS)}]" in text


def test_glm_moe_dsa_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """GLM-5's decode step at the published widths (d 6144, 64 heads over
    one latent row of 576 stored as 640, 32 index heads of 128 over an
    index key a position, 16 held experts of width 2,048 of 256 routed),
    20 lanes over 36,864 positions in pages of 64, depth cut to the dense
    layer and one expert layer: a layer scores the lanes' index keys
    through the index kernel and attends under the choice through the
    latent kernel's walk (one call each a layer; the expert layer two
    grouped matmuls), builds no ``[lanes, context, ...]`` gather of
    either pool, and both pools go out in the buffers they came in."""
    from ray_tpu.models import glm_moe_dsa
    from ray_tpu.serve.llm.engine import decode_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = glm_moe_dsa.GlmMoeDsaConfig.glm5_6l_ep16(n_layer=2)
    B, C, block, slots = 20, 36864, 64, 458752 + 64
    spec = glm_moe_dsa.cache_spec(cfg, block)
    assert spec.names == ("k_pages", "index_k") and spec.row_width == 640 and not spec.v_pool

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def shaped(tree):
        return jax.tree_util.tree_map(lambda x: arr(x.shape, x.dtype), tree)

    params = shaped(jax.eval_shape(lambda: glm_moe_dsa.init_params(cfg)))
    key = shaped(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    cache = [arr((2, slots, 640), cfg.dtype), arr((2, slots, 128), cfg.dtype)]
    compiled = jax.jit(lambda *a: decode_step(cfg, 0, block, spec, *a), donate_argnums=(1, 2)).lower(
        params, *cache, arr((B,), jnp.int32), arr((B,), jnp.int32), arr((B, C // block), jnp.int32),
        arr((B,), jnp.int32), arr((B,), jnp.float32), key).compile()
    text = compiled.as_text()
    calls = [c.split(".")[0] for c in _kernel_calls(text)]
    assert calls.count("dsa_index_paged_scores") == 2 and calls.count("mla_sparse_paged_decode_attention") == 2
    assert calls.count("moe_gmm") == 2 and len(calls) == 6
    # the gather path's contexts: 20 lanes x 36,864 rows of either pool
    assert f"bf16[{B},{C},640]" not in text and f"bf16[{B},{C},128]" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * slots * (640 + 128) * 2
    # the lanes' scores, their sortable keys and both layers' masks over 36,864 positions (2.9 MB a
    # float32 [20, 36864], the choice's passes hold several), the logits, the experts' rows: 103 MB
    # read, where one lane's latent rows gathered for 20 lanes would be 944 MB a layer
    assert mem.temp_size_in_bytes < 128e6
    assert f"s32[{B + len(glm_moe_dsa.COUNTERS)}]" in text


def test_kda_decode_step_compiles_for_v5e(one_chip):
    """The gated-delta-rule decode kernel alone at the served shape (256
    lanes of 32 heads of 128 x 128 float32: 2 MB a lane, two in and two
    out of them 8 MB of the kernel's VMEM): one custom call under its own
    name, the states going out in the buffer they came in, nothing of a
    state's size among the temporaries."""
    from ray_tpu.ops import pallas_kda

    lanes, H, dk, dv = 256, 32, 128, 128
    assert pallas_kda.kernel_takes(H, dk, dv)

    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(pallas_kda.kda_decode_step, donate_argnums=(5,)).lower(
        arr((lanes, H, dk), jnp.bfloat16), arr((lanes, H, dk), jnp.bfloat16), arr((lanes, H, dv), jnp.bfloat16),
        arr((lanes, H, dk)), arr((lanes, H)), arr((lanes, H, dk, dv)), arr((lanes,), jnp.bool_)).compile()
    calls = _kernel_calls(compiled.as_text())
    assert len(calls) == 1 and calls[0].startswith("kda_decode_step")
    lane_bytes = H * dk * dv * 4
    assert 4 * lane_bytes < pallas_kda._VMEM_BYTES
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == lanes * lane_bytes
    assert mem.temp_size_in_bytes < 16 * lane_bytes


def test_kimi_linear_programs_compile_for_v5e(one_chip, monkeypatch):
    """Kimi-Linear's two programs at the published widths (d 2304; KDA 32
    heads of 128 x 128, convolutions of 4; 32 latent heads of 128 + 64
    over one row of 576 stored as 640; 32 held experts of width 1,024 of
    256 routed), 256 lanes over 40,960 positions in pages of 64, depth
    cut to one layer of each mixer (K dense, A experts): the decode step
    updates the lanes' states through the KDA kernel INTO the donated
    array, walks the latent pages 32 lanes a call (the operands of 256
    lanes do not fit one call's VMEM) at half the compute block, and
    builds no ``[lanes, context, 640]`` gather; a 2,048-token chunk
    compiles with the chunked delta rule and the attention's XLA loop (a
    head's 192 query columns are not whole lane tiles: no chunk kernel)
    and fits beside the cache."""
    from ray_tpu.models import kimi_linear as kimi
    from ray_tpu.serve.llm.engine import decode_step, prefill_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = kimi.KimiLinearConfig.kimi_linear_48b_a3b_8l_ep8(mixer_types=("K", "A"))
    B, C, block, T, slots = 256, 40960, 64, 2048, 1441792 + 64
    spec = kimi.cache_spec(cfg, block)
    assert spec.names == ("k_pages", "kda_tail_q_0", "kda_tail_k_0", "kda_tail_v_0", "kda_state_0")

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def shaped(tree):
        return jax.tree_util.tree_map(lambda x: arr(x.shape, x.dtype), tree)

    params = shaped(jax.eval_shape(lambda: kimi.init_params(cfg)))
    key = shaped(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    cache = [arr((1, slots, 640), cfg.dtype)] + [arr((B, *shape), dtype) for _, shape, dtype in spec.lane_state]
    held = tuple(range(1, 1 + len(cache)))
    state_bytes = B * 32 * 128 * 128 * 4

    decode = jax.jit(lambda *a: decode_step(cfg, 0, block, spec, *a), donate_argnums=held).lower(
        params, *cache, arr((B,), jnp.int32), arr((B,), jnp.int32), arr((B, C // block), jnp.int32),
        arr((B,), jnp.int32), arr((B,), jnp.float32), key).compile()
    text = decode.as_text()
    calls = [c.split(".")[0] for c in _kernel_calls(text)]
    assert calls.count("kda_decode_step") == 1 and calls.count("mla_paged_decode_attention") == B // 32
    assert calls.count("moe_gmm") == 2 and len(calls) == 3 + B // 32
    assert f"bf16[{B},{C},640]" not in text
    mem = decode.memory_analysis()
    assert mem.alias_size_in_bytes >= state_bytes + slots * 640 * 2 + 3 * B * 12288 * 2
    assert mem.temp_size_in_bytes < state_bytes  # no second array of states among the temporaries
    assert f"s32[{B + len(kimi.COUNTERS)}]" in text

    chunk = jax.jit(lambda *a: prefill_step(cfg, 0, block, spec, *a), donate_argnums=held).lower(
        params, *cache, arr((1, T), jnp.int32), arr((T,), jnp.int32), arr((1,), jnp.int32),
        arr((1,), jnp.float32), key, arr((), jnp.int32), arr((C // block,), jnp.int32),
        arr((), jnp.int32)).compile()
    text = chunk.as_text()
    calls = [c.split(".")[0] for c in _kernel_calls(text)]
    assert "mla_chunk_attention" not in calls and calls.count("kda_chunk_scan") == 1
    assert "while/body/mla.attend" in text and "kda.chunk" in text
    # the delta rule's solve and its float32 powers a sub-block are the kernel's, in VMEM
    assert "InvertDiagBlocksLowerTriangular" not in text and not re.search(r"f32\[[0-9,]*16,16,128\]", text)
    mem = chunk.memory_analysis()
    print("kimi chunk temp bytes", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < 160 * 2**20  # 113 MiB read; 577 MiB with the delta rule in XLA
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 2**30
