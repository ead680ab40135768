"""ray_tpu.ops — fused/parallel kernels for the TPU compute path.

The reference has no equivalent (intra-model compute is delegated to
torch); here kernels are first-class: attention (XLA reference impl +
Pallas flash kernel; decode attention over the serving engine's paged
KV pool, read in place, all of a lane's pages or a chosen few),
block-sparse selection (block_sparse), the lightning recurrence
(lightning), ring attention for sequence/context parallelism
(reference capability gap called out in SURVEY.md §5), and collective
helpers.
"""

__all__ = ["attention", "ring_attention", "pallas_attention", "pallas_paged_attention",
           "pallas_sparse_paged_attention", "block_sparse", "lightning"]


def __getattr__(name):
    if name in __all__:
        import importlib

        return importlib.import_module(f"ray_tpu.ops.{name}")
    raise AttributeError(name)
