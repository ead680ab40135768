"""Regex partition rules: parameter paths -> PartitionSpecs.

The fmengine `match_partition_rules` shape (SNIPPETS.md [1][3]): an
ordered list of ``(path_regex, spec)`` pairs is searched first-match-wins
against each leaf's flattened ``a/b/c`` path.  Scalars and size-1 leaves
are always replicated; a leaf no rule matches is a TYPED error — silent
replication of a 2 GB embedding is exactly the bug class this plane
exists to remove.

This is the one table that maps a leaf to a ``PartitionSpec``.  It is
config-first (specs are plain tuples of axis names so a
``ShardingConfig`` pickles into trainer state and travels to workers),
uses the trainer-facing ``("batch", "model")`` axis vocabulary (the
``model`` axis is also the one ``ops.attention`` splits heads over), and
*refuses* unmatched leaves instead of defaulting them.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

SpecTuple = Tuple[Optional[Any], ...]
Rule = Tuple[str, SpecTuple]


class UnmatchedParamError(ValueError):
    """A parameter leaf matched no partition rule.  Carries every
    unmatched path so one failure names the whole gap, not the first
    leaf of it."""

    def __init__(self, paths: Sequence[str]):
        self.paths = list(paths)
        preview = ", ".join(self.paths[:8])
        more = f" (+{len(self.paths) - 8} more)" if len(self.paths) > 8 else ""
        super().__init__(
            f"{len(self.paths)} parameter leaf(s) matched no partition rule: "
            f"{preview}{more} — add a rule (a final catch-all like "
            f"(r'.*', ()) makes replication explicit)"
        )


@dataclass
class ShardingConfig:
    """GSPMD layout declaration carried by JaxTrainer.

    ``mesh`` names the axes (first axis is the data/batch axis by
    convention); ``mesh_shape`` maps axis -> size with at most one -1
    meaning "absorb the remaining devices".  ``partition_rules`` is the
    ordered ``(regex, spec_tuple)`` table; ``None`` selects the tested
    GPT-2 rule set (:func:`gpt2_partition_rules`).
    """

    mesh: Tuple[str, ...] = ("batch", "model")
    mesh_shape: Optional[Dict[str, int]] = None
    partition_rules: Optional[List[Rule]] = None
    batch_axis: str = "batch"

    def __post_init__(self):
        if self.batch_axis not in self.mesh:
            raise ValueError(
                f"batch_axis {self.batch_axis!r} not in mesh axes {self.mesh}"
            )
        if self.mesh_shape is not None:
            unknown = [a for a in self.mesh_shape if a not in self.mesh]
            if unknown:
                raise ValueError(
                    f"mesh_shape names axes {unknown} not in mesh {self.mesh}"
                )

    def rules(self) -> List[Rule]:
        return (
            list(self.partition_rules)
            if self.partition_rules is not None
            else gpt2_partition_rules()
        )

    def resolve_shape(self, n_devices: int) -> Dict[str, int]:
        """Axis -> size over ``n_devices``.  Default: the model axis
        takes the largest power of two <= 8 that divides the device
        count (one ICI ring on a v5e host), batch absorbs the rest."""
        if self.mesh_shape:
            shape = dict(self.mesh_shape)
            # A partial shape ({"model": 2} on 8 devices) must not
            # silently idle devices: the batch axis absorbs the
            # remainder unless pinned (or another axis already carries
            # the -1); unnamed model axes default to 1.
            for a in self.mesh:
                if a == self.batch_axis and -1 not in shape.values():
                    shape.setdefault(a, -1)
                else:
                    shape.setdefault(a, 1)
            return shape
        model_axes = [a for a in self.mesh if a != self.batch_axis]
        shape = {self.batch_axis: -1}
        if model_axes:
            size = 1
            for cand in (8, 4, 2):
                if n_devices % cand == 0:
                    size = cand
                    break
            shape[model_axes[0]] = size
            for extra in model_axes[1:]:
                shape[extra] = 1
        return shape


def gpt2_partition_rules() -> List[Rule]:
    """Tested rule set for ``models/gpt2.py`` over a (batch, model) mesh:
    Megatron pairing — mlp-up shards its OUTPUT dim over ``model``,
    attn-out/mlp-down their INPUT dim, so activations cross the mesh
    only at block boundaries; embeddings shard the vocab dim; norms and
    biases replicate.

    The fused ``qkv`` kernel [d, 3d] is stored by ROWS.  Its columns run
    q | k | v, so no block of them holds a head's q, k and v, and a
    column sharding makes the projection's outputs and their gradients
    cross ``model`` before the attention kernel.  ``models/gpt2.py``
    instead exchanges the row shard for the device's own heads' columns
    (one all-to-all of the weights in the compute dtype, the gradient
    back the same way) and computes q, k and v where their heads are:
    the weights are a fifth of those activations' bytes at 8 x 1024
    tokens, and do not grow with the batch."""
    return [
        (r"wte/embedding", ("model", None)),
        (r"wpe/embedding", (None, None)),
        (r"(qkv|c_attn)/kernel", ("model", None)),
        (r"(attn_out|c_proj)/kernel", ("model", None)),
        (r"(mlp_up|c_fc)/kernel", (None, "model")),
        (r"(mlp_down|fc_out)/kernel", ("model", None)),
        (r"lm_head/kernel", (None, "model")),
        (r"(ln_1|ln_2|ln_f)/(scale|bias)", ()),
        (r"bias", ()),
    ]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def match_partition_rules(
    rules: Sequence[Rule], params: Any, mesh=None, strict: bool = True
) -> Any:
    """PartitionSpec pytree for ``params`` under first-match-wins rules.

    * scalar / size-1 leaves -> replicated (never worth a collective);
    * the matched spec is clipped/padded to the leaf's rank;
    * with ``mesh`` given, axes absent from the mesh or not dividing
      their dim are dropped (a 2-device model axis on an odd vocab pads
      nothing — it replicates that dim instead of crashing XLA);
    * any leaf matching NO rule raises :class:`UnmatchedParamError`
      naming every gap at once (``strict=False`` replicates instead —
      for derived trees like optimizer state, where moment leaves match
      the param rules through their path suffix and the schedule
      scalars should just replicate).
    """
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    compiled = [(re.compile(pat), tuple(spec)) for pat, spec in rules]
    unmatched: List[str] = []

    def one(path, leaf):
        name = _path_str(path)
        shape = tuple(getattr(leaf, "shape", ()))
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return P()
        for pat, spec in compiled:
            if pat.search(name):
                return _clip(spec, shape, mesh, P)
        unmatched.append(name)
        return P()

    out = jax.tree_util.tree_map_with_path(one, params)
    if unmatched and strict:
        raise UnmatchedParamError(unmatched)
    return out


def _clip(spec: SpecTuple, shape: Tuple[int, ...], mesh, P):
    parts = list(spec)[: len(shape)]
    parts += [None] * (len(shape) - len(parts))
    if mesh is not None:
        out = []
        for dim, axis in zip(shape, parts):
            if axis is None or axis not in mesh.shape:
                out.append(None)
            elif dim % mesh.shape[axis] == 0:
                out.append(axis)
            else:
                out.append(None)
        parts = out
    return P(*parts)
