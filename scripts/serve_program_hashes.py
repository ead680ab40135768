"""Whether a change left the serve programs of the families that exist
what they were (PERF.md section 6, PR 33, PR 38, PR 40, PR 41, PR 45, PR 50, PR 54, PR 57 and PR 61).

    JAX_PLATFORMS=cpu PYTHONPATH=<checkout> python scripts/serve_program_hashes.py out.json

run from the root of the parent's checkout and of the change's, then the
two files compared.  ``serve_prefill`` and ``serve_decode`` of each
preset of CELLS are lowered at their cells' shapes for a described v5e
(no chip, nothing compiled) and the StableHLO text is hashed twice:
``raw`` as it is, and ``stripped`` with each Mosaic payload replaced by
the hash of its MLIR printed without locations.  A payload carries the
file paths and line numbers of every frame, so two checkouts at
different paths never agree on ``raw`` for a program with a kernel;
``stripped`` is the same on both sides exactly when the programs are.
``scripts/moe_combine_check.py`` imports ``lowered`` to compile and list
one of these programs by named scope."""
import base64, hashlib, json, os, re, sys
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from ray_tpu.serve.llm.config import MODEL_FAMILIES, LLMConfig, model_family
from ray_tpu.serve.llm.engine import decode_step, prefill_step

jax.default_backend = lambda: "tpu"
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
def arr(shape, dtype): return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)
def shaped(tree): return jax.tree_util.tree_map(lambda x: arr(x.shape, x.dtype), tree)

CELLS = {  # preset: lanes, block, pool tokens, max context, prefill tokens
    "large": (16, 16, 16384, 1024, 256),
    "olmoe_1b_7b_12l": (32, 16, 32768, 4096, 256),
    "minicpm_sala_16l": (16, 16, 540672, 33792, 4096),
    "mistral_small_4_6l_ep4": (48, 64, 786432, 36864, 4096),
    "nemotron_3_nano_26l_ep4": (128, 64, 393216, 6144, 2048),
    "granite_4_0_h_small_10l_ep2": (32, 64, 557056, 17408, 2048),
    "mellum2_12b_a2_5b_12l": (32, 64, 393216, 34816, 2048),
    "jamba2_3b": (256, 64, 786432, 8192, 2048),
    "zaya1_8b_20l": (48, 64, 229376, 16384, 2048),
    "glm5_6l_ep16": (20, 64, 458752, 36864, 4096),
    "kimi_linear_48b_a3b_8l_ep8": (256, 64, 1835008, 40960, 2048),
}

def strip_payloads(text):
    from jax._src.lib.mlir import ir
    from jax._src.lib import tpu  # registers the dialects
    from jax._src.interpreters import mlir as jmlir
    def unescape(t):
        return re.sub(r"\\([0-9A-Fa-f]{2})", lambda h: chr(int(h.group(1), 16)), t)
    def repl(m):
        cfg = json.loads(unescape(m.group(1)))
        body = base64.b64decode(cfg["custom_call_config"]["body"])
        with jmlir.make_ir_context() as ctx:
            try:
                tpu.register_dialect(ctx)
            except Exception:
                pass
            ctx.allow_unregistered_dialects = True
            mod = ir.Module.parse(body)
            asm = mod.operation.get_asm(enable_debug_info=False)
        cfg["custom_call_config"]["body"] = "sha256:" + hashlib.sha256(asm.encode()).hexdigest()[:16]
        return "backend_config = <" + json.dumps(cfg, sort_keys=True) + ">"
    return re.sub(r'backend_config = "((?:[^"\\]|\\.)*)"', lambda m: repl(m) if "custom_call_config" in m.group(1) else m.group(0), text)

def lowered(preset):
    """{"serve_prefill", "serve_decode"}: the preset's two programs lowered
    at its cell's shapes; None for a checkout from before the family."""
    B, block, pool_tokens, max_ctx, T = CELLS[preset]
    if not any(preset in presets for *_, presets in MODEL_FAMILIES):
        return None
    cfg = LLMConfig(model=preset, dtype="bfloat16").model_config()
    family = model_family(cfg)
    spec = family.cache_spec(cfg, block)
    params = shaped(jax.eval_shape(lambda: family.serving_params(family.init_params(cfg), cfg)))
    key = shaped(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    slots, blocks = pool_tokens + block, pool_tokens // block + 1
    pool = arr((spec.paged_layers, slots, spec.row_width), cfg.dtype)
    cache = [pool] + ([pool] if spec.v_pool else [])
    cache += [arr((spec.paged_layers, blocks * rows, width), dtype) for _, rows, width, dtype in spec.page_extras]
    cache += [arr((B, *shape), dtype) for _, shape, dtype in spec.lane_state]
    held = tuple(range(1, 1 + len(cache)))
    pages = -(-max_ctx // block)
    chunk = [arr((), jnp.int32), arr((pages,), jnp.int32), arr((), jnp.int32)] if spec.reads_cache else []
    return {
        "serve_prefill": jax.jit(lambda *a: prefill_step(cfg, 0, block, spec, *a), donate_argnums=held).lower(
            params, *cache, arr((1, T), jnp.int32), arr((T,), jnp.int32), arr((1,), jnp.int32),
            arr((1,), jnp.float32), key, *chunk),
        "serve_decode": jax.jit(lambda *a: decode_step(cfg, 0, block, spec, *a), donate_argnums=held).lower(
            params, *cache, arr((B,), jnp.int32), arr((B,), jnp.int32), arr((B, pages), jnp.int32),
            arr((B,), jnp.int32), arr((B,), jnp.float32), key),
    }

def main(path):
    out = {}
    for preset in CELLS:
        for name, program in (lowered(preset) or {}).items():
            text = program.as_text()
            kernels = text.count("tpu_custom_call")
            stripped = strip_payloads(text) if kernels else text
            out[f"{preset}.{name}"] = {"raw": hashlib.sha256(text.encode()).hexdigest()[:16],
                                       "stripped": hashlib.sha256(stripped.encode()).hexdigest()[:16],
                                       "kernels": kernels, "chars": len(text)}
            print(preset, name, out[f"{preset}.{name}"], flush=True)
    json.dump(out, open(path, "w"), indent=1)

if __name__ == "__main__":
    main(sys.argv[1])
