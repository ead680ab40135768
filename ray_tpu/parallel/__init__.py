"""ray_tpu.parallel — device meshes.

This is the TPU-native replacement for the reference's torch DDP/FSDP
wrappers and NCCL process groups (reference:
python/ray/train/torch/train_loop_utils.py:162 prepare_model,
python/ray/train/torch/config.py:153): instead of wrapping a model in a
communication library, we place arrays on a `jax.sharding.Mesh` and let
XLA insert ICI collectives.  How a parameter tree is laid out on a mesh
and how a step is jitted over it is ``ray_tpu.train.sharding``'s alone.
"""

from ray_tpu.parallel.mesh import MeshConfig, create_mesh

__all__ = ["MeshConfig", "create_mesh"]
