"""Plan -> PartitionSpec mapping (the paper's mode decision, executed).

``Shardings`` is the single object the launchers hand to jit: it turns the
ExecutionPlan's per-stage SPATIAL/TEMPORAL decision into Megatron-oriented
parameter specs (column-parallel QKV/up projections, row-parallel output/down
projections), decode-cache specs (KV heads over ``model`` when divisible,
else the sequence dim), batch specs (TEMPORAL folds the model axis into data
parallelism), and named activation constraints for the forward pass.

Every spec passes through the ``_fit`` divisibility safety net: an axis whose
extent does not divide the dim is dropped to ``None`` rather than letting
GSPMD pad or error — the reduced smoke configs exercise exactly this path.
"""
from __future__ import annotations

import logging
import math
from typing import Any, Optional, Sequence

import jax
import jax.tree_util as jtu
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.plan import SPATIAL, ExecutionPlan

PyTree = Any

logger = logging.getLogger(__name__)

# Megatron orientation by leaf name.  Column-parallel weights shard their
# output (last) dim; row-parallel weights shard their input (second-to-last)
# dim so the pair needs one collective per stage, not two.
COLUMN_PARALLEL = frozenset(
    {"wqkv", "wq", "wk", "wv", "w1", "w3", "w_x", "w_g", "w_r", "w_k", "w_v"}
)
ROW_PARALLEL = frozenset({"wo", "w2", "w_out", "w_o"})

ACT_NAMES = ("act_hidden", "act_heads", "act_kv", "act_heads_flat")


def _key_names(path: Sequence) -> list[str]:
    """Stringified key path (DictKey / SequenceKey / GetAttrKey / raw str)."""
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "name"):
            names.append(str(k.name))
        elif hasattr(k, "idx"):
            names.append(str(k.idx))
        else:
            names.append(str(k))
    return names


class Shardings:
    """Sharding rules for one (mesh x plan x arch) accelerator instance.

    Spec-level methods (``param_spec``, ``cache_spec``, ``_fit``,
    ``batch_axes_for``) only read ``mesh.shape`` so they work on shape-only
    mesh stand-ins; ``*_shardings``/``constrain`` need a real mesh.
    """

    def __init__(self, mesh, plan: ExecutionPlan, cfg):
        self.mesh = mesh
        self.plan = plan
        self.cfg = cfg
        self.axis_sizes = dict(mesh.shape)
        self._fit_warned: set = set()  # (dim, axes) pairs already reported

    # ------------------------------------------------------------- helpers
    def _axis(self, name: str) -> int:
        return self.axis_sizes.get(name, 1)

    def _ns(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def _fit(self, spec: P, shape: Sequence[int]) -> P:
        """Divisibility safety net: drop mesh axes a dim cannot host.

        Each drop is logged once per (dim extent, axes) pair so a
        misconfigured mesh (e.g. 9 heads on a 16-wide model axis) is
        debuggable instead of silently running replicated.
        """
        out = []
        for i, entry in enumerate(spec):
            if entry is None:
                out.append(None)
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            if any(a not in self.axis_sizes for a in axes):
                out.append(None)
                continue
            size = math.prod(self._axis(a) for a in axes)
            ok = i < len(shape) and size > 0 and shape[i] % size == 0
            if not ok:
                dim = shape[i] if i < len(shape) else None
                key = (dim, axes)
                if key not in self._fit_warned:
                    self._fit_warned.add(key)
                    logger.warning(
                        "Shardings safety net: dim %s (index %d of shape %s) "
                        "does not divide over mesh axes %s (extent %d); "
                        "dropping to replicated for arch=%s",
                        dim, i, tuple(shape), axes, size, self.plan.arch,
                    )
            out.append(entry if ok else None)
        return P(*out)

    def _dp_axes(self) -> tuple[str, ...]:
        """Mesh axes that carry data parallelism, outermost first."""
        axes = []
        if self._axis("pod") > 1 and self.plan.pod_role == "data":
            axes.append("pod")
        axes.append("data")
        if self.plan.dp_over_model:
            axes.append("model")  # TEMPORAL: serial PRGs use ALL chips (FSDP)
        return tuple(axes)

    def batch_axes_for(self, batch: int) -> Optional[tuple[str, ...]]:
        """Largest dp-axis prefix the global batch divides, or None."""
        axes = list(self._dp_axes())
        while axes:
            size = math.prod(self._axis(a) for a in axes)
            if batch > 0 and batch % size == 0:
                return tuple(axes)
            axes.pop()
        return None

    @staticmethod
    def _entry(axes: Optional[tuple[str, ...]]):
        if not axes:
            return None
        return axes if len(axes) > 1 else axes[0]

    # ------------------------------------------------------------ parameters
    def param_spec(self, path: Sequence, leaf) -> P:
        """PartitionSpec for one parameter leaf, identified by its tree path.

        Leading stack dims (scanned pattern-groups) are absorbed by indexing
        dims from the end of the shape.
        """
        names = _key_names(path)
        name = names[-1] if names else ""
        shape = tuple(leaf.shape)
        nd = len(shape)
        if nd <= 1:
            return P(*([None] * nd))
        spec: list = [None] * nd

        stage = "mha" if ("attn" in names or "cross" in names) else "ffn"
        mode = self.plan.mode_for(stage)
        is_moe_w = self.cfg.is_moe and "ffn" in names and name in ("w1", "w2", "w3", "router")

        if name == "embed":
            if self.plan.embed_shard == "vocab":
                spec[-2] = "model"
            elif self.plan.embed_shard == "embed":
                spec[-1] = "model"
        elif name in ("lm_head", "cls_head"):
            if name == "lm_head" and self.plan.embed_shard == "vocab":
                spec[-1] = "model"
        elif is_moe_w:
            if name != "router":  # router (d, E) is tiny: keep replicated
                if self.plan.moe_mode == "ep" and nd >= 3:
                    spec[-3] = "model"  # experts on the stacked leading dim
                elif self.plan.moe_mode == "tp":
                    spec[-2 if name == "w2" else -1] = "model"
        elif mode == SPATIAL:
            if name in COLUMN_PARALLEL:
                spec[-1] = "model"
                if self.plan.zero_weights:
                    spec[-2] = "data"
            elif name in ROW_PARALLEL:
                spec[-2] = "model"
                if self.plan.zero_weights:
                    spec[-1] = "data"
        else:  # TEMPORAL: no tensor parallelism; ZeRO-shard weights over DP
            if (self.plan.dp_over_model or self.plan.zero_weights) and name in (
                COLUMN_PARALLEL | ROW_PARALLEL
            ):
                axes = self._dp_axes() if self.plan.dp_over_model else ("data",)
                spec[-1] = self._entry(axes)
        # Pipeline pods: the stacked layer-group dim is the stage dim — each
        # pod holds n_groups/n_stage groups, exactly the per-stage slice
        # dist.pipeline.pipeline_forward consumes (in_specs P("pod", ...)).
        if (
            self.plan.pod_role == "pipeline"
            and self._axis("pod") > 1
            and len(names) >= 2
            and names[0] == "blocks"
            and names[1] == "stack"
        ):
            spec[0] = "pod"
        return self._fit(P(*spec), shape)

    def param_shardings(self, params: PyTree) -> PyTree:
        return jtu.tree_map_with_path(lambda p, leaf: self._ns(self.param_spec(p, leaf)), params)

    def stack_specs(self, stack: PyTree) -> PyTree:
        """Raw PartitionSpecs for the ``blocks.stack`` subtree.

        shard_map ``in_specs`` for the manual-collective layer paths (the
        Megatron-SP stack and the pipeline scheduler) — the same rules as
        ``param_spec`` but without wrapping in NamedSharding, and with the
        path re-rooted at ``blocks.stack`` so leaf names resolve.
        """
        prefix = (jtu.DictKey("blocks"), jtu.DictKey("stack"))
        return jtu.tree_map_with_path(
            lambda p, leaf: self.param_spec(prefix + tuple(p), leaf), stack
        )

    # ------------------------------------------------------------ decode cache
    def cache_spec(self, path: Sequence, leaf) -> P:
        """Decode-cache leaf spec: batch over data; KV heads over ``model``
        when divisible, else the sequence dim (long-context serving).

        Paged pool leaves (continuous batching) have no batch dim — blocks
        are shared by every request — so only the KV-head dim shards (the
        GSPMD-constrained serve path of the ROADMAP's SP decode item); the
        block dim stays replicated because block ids are global."""
        names = _key_names(path)
        name = names[-1] if names else ""
        shape = tuple(leaf.shape)
        nd = len(shape)
        spec: list = [None] * nd
        if "paged" in names:
            if name in ("k", "v") and nd >= 4:
                spec[-2] = "model"  # (..., n_blocks, block, KV, Dh)
            # int8 scale rows (..., n_blocks, 1, P) interleave every head
            # on the lanes: they stay replicated
            return self._fit(P(*spec), shape)
        if "cross_kv" in names and nd >= 4:
            spec[-4] = "data"  # encoder memory kv: batch only
        elif name in ("k", "v") and nd >= 4:
            spec[-4] = "data"
            if self.cfg.n_kv_heads % max(self._axis("model"), 1) == 0:
                spec[-2] = "model"
            else:
                spec[-3] = "model"  # shard the sequence dim instead
        elif name == "S" and nd >= 4:
            spec[-4] = "data"  # rwkv state (B, H, Dh, Dh)
        elif name in ("h", "shift", "cmix") and nd >= 2:
            spec[-2] = "data"
        elif name == "conv" and nd >= 3:
            spec[-3] = "data"
        elif name == "memory" and nd >= 3:
            spec[-3] = "data"
        return self._fit(P(*spec), shape)

    def cache_shardings(self, cache: PyTree) -> PyTree:
        return jtu.tree_map_with_path(lambda p, leaf: self._ns(self.cache_spec(p, leaf)), cache)

    # ------------------------------------------------------------ batch inputs
    def batch_spec(self, leaf) -> P:
        shape = tuple(leaf.shape)
        spec: list = [None] * len(shape)
        if not shape:
            return P()
        spec[0] = self._entry(self.batch_axes_for(shape[0]))
        if self.plan.seq_shard and len(shape) >= 2:
            spec[1] = "data"  # long-context: batch < data axis, split the seq
        return self._fit(P(*spec), shape)

    def batch_shardings(self, batch: PyTree) -> PyTree:
        return jax.tree.map(lambda leaf: self._ns(self.batch_spec(leaf)), batch)

    # ------------------------------------------------------------ activations
    def act_spec(self, name: str, shape: Sequence[int]) -> P:
        spec: list = [None] * len(shape)
        if not shape:
            return P()
        spec[0] = self._entry(self.batch_axes_for(shape[0]))
        spatial_mha = self.plan.mode_for("mha") == SPATIAL
        if name == "act_hidden":
            if self.plan.seq_shard and len(shape) >= 2:
                spec[1] = "data"
            elif self.plan.seq_parallel_acts and len(shape) >= 2:
                spec[1] = "model"
        elif name == "act_heads" and len(shape) >= 3:
            if spatial_mha and self.plan.head_shards > 1:
                spec[-2] = "model"
        elif name == "act_kv" and len(shape) >= 3:
            if spatial_mha:
                spec[-2] = "model"
        elif name == "act_heads_flat":
            if spatial_mha and self.plan.head_shards > 1:
                spec[-1] = "model"
        return self._fit(P(*spec), shape)

    def constrain(self, x, name: Optional[str] = None):
        """The ``shard`` callable threaded through forward/train/serve."""
        if name not in ACT_NAMES or not hasattr(x, "shape"):
            return x
        spec = self.act_spec(name, x.shape)
        return jax.lax.with_sharding_constraint(x, self._ns(spec))
