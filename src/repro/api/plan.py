"""The declarative ``ServerPlan`` API — one validated specification of the
paper's whole server step, composed once and run everywhere.

Algorithm 1 is a *composition*: clip the received gradient differences,
optionally compress, optionally Bucketing, then a robust aggregator — and
the Section-6 heuristic shows the same clip wrapper adapts ANY robust rule
to partial participation.  Before this module that composition was
stringly-typed and re-wired per caller ("bucket_"-prefixed rule names,
five orthogonal ``ByzTrainConfig`` knobs, per-engine clip+aggregate
plumbing).  A ``ServerPlan`` states it once as structured stages:

    plan = ServerPlan(
        aggregate=AggregatorSpec("krum", byz_bound=1),
        clip=ClipSpec(alpha=2.0),          # lambda_k = alpha * ||x^k - x^{k-1}||
        bucket=BucketSpec(s=2),            # Karimireddy et al. Bucketing
        schedule=ScheduleSpec(placement="sharded", blocks="pipelined",
                              superleaf_elems=65536, backend="auto"),
    )
    step = plan.build(mesh)                # -> ServerStep callable
    g_new = g + step(msgs, mask=sampled, key=k, radius=lam)

Cross-stage constraints are validated at CONSTRUCTION (``PlanError``, a
``ValueError`` subclass):

  - the pipelined block schedule needs the sharded placement (naive has no
    per-block collectives to overlap);
  - superleaf packing on an iterative rule (centered_clip / rfa) warns
    (``PlanWarning``) that uniform chunks REPLACE per-tensor leaves as the
    robust-aggregation block partition;
  - ``m_select`` is a multi_krum parameter (plain Krum selects one row);
  - trim_ratio / bucket size / cohort / backend / placement ranges.

Worker-count checks that need the mesh happen at ``build(mesh)`` (cohort
vs. worker count) and at call time (one worker row per mesh worker).

``plan.build(mesh=None)`` compiles the plan into a :class:`ServerStep`:

  - ``mesh=None`` — the simulation-engine form: whole-message semantics on
    an (n, d) matrix or a worker-stacked pytree, backed by the dispatch
    layer's fused ``clip_then_aggregate`` kernels.
  - ``mesh=...``  — the distributed form: the naive or sharded collective
    schedule (scatter -> fused kernel -> gather, optionally double-buffered
    and superleaf-packed) with whole-tree two-phase selection; see
    :mod:`repro.api.mesh_exec`.

``plan.estimate(shapes, n_workers=...)`` reuses the benchmark traffic
models for bytes / steady-state block cost introspection without running
anything.  ``to_json`` / ``from_json`` give plans a canonical serialized
name (benchmark configs, CI perf-gate rows, ``--plan-json`` CLIs, the
serving wire format).  The document carries a ``"version"`` field
(currently 1); ``from_json`` treats missing versions as v1 and rejects
unknown ones, so the wire format can evolve without silently
misinterpreting old documents.
"""
from __future__ import annotations

import dataclasses
import json
import warnings
from typing import Optional

import jax.numpy as jnp

from ..core.aggregators import (
    RULE_ALIASES as _CORE_ALIASES,
    Aggregator,
    make_aggregator,
)
from ..core.compressors import Compressor, make_compressor

__all__ = [
    "PlanError",
    "PlanWarning",
    "ClipSpec",
    "CompressSpec",
    "BucketSpec",
    "AggregatorSpec",
    "ScheduleSpec",
    "ServerPlan",
    "ServerStep",
    "PLAN_VERSION",
]

# canonical plan-document version.  Bump when the JSON schema changes in a
# way old readers would misinterpret; ``from_dict`` accepts documents with
# no version field as v1 (every document written before versioning).
PLAN_VERSION = 1


class PlanError(ValueError):
    """A ServerPlan (or one of its specs) failed validation."""


class PlanWarning(UserWarning):
    """A ServerPlan combination is valid but changes semantics subtly."""


# canonical rule names = the core registry; aliases are the legacy mesh
# spellings that predate the plan API
_RULES = ("mean", "cm", "trimmed_mean", "rfa", "krum", "multi_krum",
          "centered_clip")
_RULE_ALIASES = dict(_CORE_ALIASES, geometric_median="rfa")
_ITERATIVE_RULES = ("centered_clip", "rfa")
_SELECTION_RULES = ("krum", "multi_krum")
_COMPRESSOR_KINDS = ("identity", "rand_k", "rand_fraction",
                     "l2_quantization")
_PLACEMENTS = ("naive", "sharded")
_BLOCKS = ("sequential", "pipelined")
_BACKENDS = ("jnp", "pallas", "auto")

_DEFAULT_ITERS = {"centered_clip": 5, "rfa": 8}


def _set(obj, **kw):
    for k, v in kw.items():
        object.__setattr__(obj, k, v)


# ---------------------------------------------------------------------------
# stage specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ClipSpec:
    """Server-side re-clip of every received message (Alg. 1 line 10).

    Exactly one of:

    ``alpha``  — the data-dependent radius multiplier: the caller computes
                 lambda_k = alpha * ||x^k - x^{k-1}|| per step (use
                 :meth:`ServerStep.radius`) and passes it as ``radius=``.
    ``radius`` — a fixed static radius, applied automatically by the built
                 step when the caller passes no per-call radius (the
                 serving endpoint's form).
    """

    alpha: Optional[float] = None
    radius: Optional[float] = None

    def __post_init__(self):
        if (self.alpha is None) == (self.radius is None):
            raise PlanError(
                "ClipSpec needs exactly one of alpha (data-dependent "
                "lambda_k = alpha * ||x^k - x^{k-1}||) or radius (fixed); "
                f"got alpha={self.alpha!r}, radius={self.radius!r}"
            )
        val = self.alpha if self.alpha is not None else self.radius
        if not (val > 0):
            raise PlanError(f"ClipSpec value must be > 0, got {val!r}")


@dataclasses.dataclass(frozen=True)
class CompressSpec:
    """Unbiased worker-side compression (Definition 2.2).

    ``kind`` is a ``repro.core.compressors`` registry name; ``rand_k``
    takes ``k`` (coordinates kept), ``rand_fraction`` takes ``frac``.
    """

    kind: str = "rand_k"
    k: int = 0
    frac: float = 0.0

    def __post_init__(self):
        if self.kind not in _COMPRESSOR_KINDS:
            raise PlanError(
                f"unknown compressor kind {self.kind!r}; have "
                f"{sorted(_COMPRESSOR_KINDS)}"
            )
        if self.kind == "rand_k" and self.k < 1:
            raise PlanError(
                f"CompressSpec(kind='rand_k') needs k >= 1, got {self.k}"
            )
        if self.kind == "rand_fraction" and not (0.0 < self.frac <= 1.0):
            raise PlanError(
                "CompressSpec(kind='rand_fraction') needs 0 < frac <= 1, "
                f"got {self.frac}"
            )


@dataclasses.dataclass(frozen=True)
class BucketSpec:
    """Bucketing composition (Algorithm 2, Karimireddy et al., 2022):
    random-permute rows, average buckets of ``s``, aggregate the bucket
    means — upgrades CM/GM/Krum to (delta, c)-ARAgg."""

    s: int = 2

    def __post_init__(self):
        if self.s < 2:
            raise PlanError(f"Bucketing needs bucket size s >= 2, got {self.s}")


@dataclasses.dataclass(frozen=True)
class AggregatorSpec:
    """The robust aggregation rule and its per-rule parameters.

    ``rule`` is a core-registry name (aliases tm/cclip/gm are normalized).
    ``trim_ratio`` applies to trimmed_mean, ``byz_bound``/``m_select`` to
    the Krum rules, ``tau``/``iters`` to centered_clip, ``iters`` to rfa
    (0 = the rule's default iteration count).
    """

    rule: str
    trim_ratio: float = 0.1
    byz_bound: Optional[int] = None
    m_select: int = 0
    tau: float = 10.0
    iters: int = 0

    def __post_init__(self):
        rule = _RULE_ALIASES.get(self.rule, self.rule)
        if rule not in _RULES:
            raise PlanError(
                f"unknown aggregator rule {self.rule!r}; have "
                f"{sorted(_RULES)} (aliases {sorted(_RULE_ALIASES)})"
            )
        _set(self, rule=rule)
        if rule == "trimmed_mean" and not (0.0 <= self.trim_ratio < 0.5):
            raise PlanError(
                f"trim_ratio must be in [0, 0.5) — trimming removes "
                f"2*ceil(trim_ratio*n) rows, so 0.5 would drop everything; "
                f"got {self.trim_ratio}"
            )
        if self.byz_bound is not None and self.byz_bound < 0:
            raise PlanError(f"byz_bound must be >= 0, got {self.byz_bound}")
        if self.m_select < 0:
            raise PlanError(f"m_select must be >= 0, got {self.m_select}")
        if self.m_select > 0 and rule != "multi_krum":
            raise PlanError(
                f"m_select is a multi_krum parameter (how many best-scored "
                f"rows to average); rule {rule!r} selects exactly one row — "
                "use rule='multi_krum' or drop m_select"
            )
        if self.tau <= 0:
            raise PlanError(f"tau must be > 0, got {self.tau}")
        if self.iters < 0:
            raise PlanError(f"iters must be >= 0, got {self.iters}")

    @property
    def resolved_iters(self) -> int:
        return self.iters or _DEFAULT_ITERS.get(self.rule, 0)


@dataclasses.dataclass(frozen=True)
class ScheduleSpec:
    """How the built step places and orders the aggregation work.

    ``placement``       — "naive" (gather everything, aggregate everywhere;
                          the paper's parameter-server semantics) or
                          "sharded" (all_to_all scatter, per-chip fused
                          kernel, all_gather; needs a mesh).
    ``blocks``          — inner block order of the sharded placement:
                          "sequential" (the equivalence oracle) or
                          "pipelined" (double-buffered: block i+1's
                          all_to_all in flight while block i's kernel
                          runs; bitwise-equal).
    ``superleaf_elems`` — > 0 packs the message pytree into uniform
                          chunks of this many coordinates (one uniform
                          dispatch per chunk) instead of ragged
                          per-tensor leaves.
    ``backend``         — aggregation kernel backend: "jnp" | "pallas" |
                          "auto" (pallas iff on TPU).
    ``worker_axes``     — mesh axes enumerating workers; () = every
                          batch-like axis (pod x data).
    """

    placement: str = "naive"
    blocks: str = "sequential"
    superleaf_elems: int = 0
    backend: str = "auto"
    worker_axes: tuple = ()

    def __post_init__(self):
        if self.placement not in _PLACEMENTS:
            raise PlanError(
                f"unknown placement {self.placement!r}; have "
                f"{sorted(_PLACEMENTS)}"
            )
        if self.blocks not in _BLOCKS:
            raise PlanError(
                f"unknown schedule {self.blocks!r}; have 'sequential', "
                "'pipelined'"
            )
        if self.superleaf_elems < 0:
            raise PlanError(
                f"superleaf_elems must be >= 0, got {self.superleaf_elems}"
            )
        if self.backend not in _BACKENDS:
            raise PlanError(
                f"unknown backend {self.backend!r}; have 'jnp', 'pallas', "
                "'auto'"
            )
        _set(self, worker_axes=tuple(self.worker_axes))


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------

_SPEC_FIELDS = {
    "clip": ClipSpec,
    "compress": CompressSpec,
    "bucket": BucketSpec,
    "aggregate": AggregatorSpec,
    "schedule": ScheduleSpec,
}


@dataclasses.dataclass(frozen=True)
class ServerPlan:
    """Declarative, validated server-step specification (module docstring).

    Stages compose in protocol order: clip -> compress -> bucket ->
    aggregate, run under ``schedule``.  ``cohort`` (optional) records the
    sampled cohort size C for worker-count validation at ``build(mesh)``.
    """

    aggregate: AggregatorSpec
    clip: Optional[ClipSpec] = None
    compress: Optional[CompressSpec] = None
    bucket: Optional[BucketSpec] = None
    schedule: ScheduleSpec = ScheduleSpec()
    cohort: Optional[int] = None

    def __post_init__(self):
        if isinstance(self.aggregate, str):
            _set(self, aggregate=AggregatorSpec(self.aggregate))
        for field, klass in _SPEC_FIELDS.items():
            v = getattr(self, field)
            if v is not None and not isinstance(v, klass):
                raise PlanError(
                    f"ServerPlan.{field} must be a {klass.__name__} or "
                    f"None, got {type(v).__name__}"
                )
        if self.cohort is not None and self.cohort < 1:
            raise PlanError(f"cohort must be >= 1, got {self.cohort}")
        # cross-stage constraints -----------------------------------------
        if (self.schedule.blocks == "pipelined"
                and self.schedule.placement != "sharded"):
            raise PlanError(
                "blocks='pipelined' requires placement='sharded': the "
                "naive placement gathers the whole message at once and has "
                "no per-block collectives to overlap — use "
                "blocks='sequential' or placement='sharded'"
            )
        if (self.schedule.superleaf_elems > 0
                and self.aggregate.rule in _ITERATIVE_RULES):
            warnings.warn(
                f"superleaf_elems={self.schedule.superleaf_elems} with the "
                f"iterative rule {self.aggregate.rule!r}: uniform chunks "
                "REPLACE per-tensor leaves as the robust-aggregation block "
                "partition (block-robust, not whole-message, semantics); "
                "set superleaf_elems=0 to keep tensor-boundary blocks",
                PlanWarning,
                stacklevel=3,
            )

    # -- worker-count validation -------------------------------------------

    def validate_workers(self, n_workers: int) -> None:
        """Raise PlanError when the plan cannot run over ``n_workers``."""
        if self.cohort is not None and self.cohort > n_workers:
            raise PlanError(
                f"cohort C={self.cohort} exceeds the {n_workers} available "
                "workers: partial participation samples C of n workers, so "
                "C must be <= n"
            )

    # -- compilation --------------------------------------------------------

    def build_aggregator(self) -> Aggregator:
        """The dispatch-layer ``Aggregator`` this plan's bucket+aggregate
        stages resolve to (identical to the legacy ``make_aggregator``
        construction — the source of legacy/plan bitwise equality)."""
        spec = self.aggregate
        kwargs = {}
        if spec.rule == "trimmed_mean":
            kwargs["trim_ratio"] = spec.trim_ratio
        if spec.rule in _SELECTION_RULES:
            kwargs["byz_bound"] = spec.byz_bound
            kwargs["m_select"] = spec.m_select
        if spec.rule == "centered_clip":
            kwargs["tau"] = spec.tau
        if spec.rule in _ITERATIVE_RULES and spec.iters:
            kwargs["iters"] = spec.iters
        return make_aggregator(
            spec.rule,
            bucket_s=self.bucket.s if self.bucket is not None else 0,
            backend=self.schedule.backend,
            **kwargs,
        )

    def build_compressor(self) -> Optional[Compressor]:
        if self.compress is None:
            return None
        c = self.compress
        kw = {}
        if c.kind == "rand_k":
            kw["k"] = c.k
        if c.kind == "rand_fraction":
            kw["frac"] = c.frac
        return make_compressor(c.kind, **kw)

    def build(self, mesh=None, *, n_workers: Optional[int] = None
              ) -> "ServerStep":
        """Compile the plan into one :class:`ServerStep` callable.

        ``mesh=None`` builds the whole-message engine form; a mesh builds
        the distributed form under ``self.schedule``.  ``n_workers`` is the
        number of message rows when it differs from the mesh's worker
        devices (several workers per device, naive placement)."""
        if mesh is None and self.schedule.placement == "sharded":
            raise PlanError(
                "placement='sharded' needs a mesh: build(mesh) runs the "
                "all_to_all schedule over the mesh's worker axes; use "
                "placement='naive' for the single-process engine form"
            )
        if mesh is not None:
            from .mesh_exec import mesh_worker_axes, mesh_worker_count

            waxes = mesh_worker_axes(mesh, self.schedule.worker_axes)
            slots = mesh_worker_count(mesh, waxes)
            if (self.schedule.placement == "sharded" and waxes
                    and n_workers and n_workers != slots):
                raise PlanError(
                    f"the sharded placement needs one worker per device: "
                    f"{n_workers} workers but the mesh has {slots} devices "
                    "on its worker axes; use placement='naive' to run "
                    "several workers per device"
                )
            self.validate_workers(n_workers or slots)
        return ServerStep(self, mesh=mesh)

    # -- introspection -------------------------------------------------------

    def estimate(self, shapes, *, n_workers: Optional[int] = None,
                 itemsize: int = 4) -> dict:
        """Modeled traffic of one server step over a message of ``shapes``.

        ``shapes`` is the per-worker message: an int coordinate count, a
        shape tuple, an array / ShapeDtypeStruct, or a pytree of those.
        Reuses the ``benchmarks.bench_kernels`` traffic models: the
        rule-family HBM model (fused vs unfused streams) plus — for the
        sharded placement — the steady-state pipeline block model.
        """
        n = n_workers if n_workers is not None else self.cohort
        if n is None:
            raise PlanError(
                "estimate needs the worker count: pass n_workers= (or set "
                "plan.cohort)"
            )
        d = _total_elems(shapes)
        try:
            from benchmarks import bench_kernels as bk
        except ImportError as e:  # pragma: no cover — repo-root package
            raise PlanError(
                "plan.estimate reuses the benchmarks traffic models; run "
                "from the repository root so `benchmarks` is importable"
            ) from e
        rule = self.aggregate.rule
        out = {
            "rule": rule,
            "n": int(n),
            "d": int(d),
            "placement": self.schedule.placement,
            "blocks": self.schedule.blocks,
            "message_bytes": int(n) * int(d) * itemsize,
        }
        if rule in _SELECTION_RULES:
            out["server_step"] = bk.traffic_model_krum(n, d, itemsize)
            out["apply_pass"] = bk.traffic_model_krum_apply(n, d, itemsize)
        elif rule in _ITERATIVE_RULES:
            out["server_step"] = bk.traffic_model_iterative(
                n, d, self.aggregate.resolved_iters, itemsize
            )
        else:
            out["server_step"] = bk.traffic_model(n, d, itemsize)
        if self.schedule.placement == "sharded":
            chunk = self.schedule.superleaf_elems or d
            out["pipeline"] = bk.traffic_model_pipeline(
                n_blocks=max(1, -(-d // chunk)), chunk=chunk, W=n,
                itemsize=itemsize,
            )
        return out

    # -- serialization -------------------------------------------------------

    def to_dict(self) -> dict:
        d = {
            "version": PLAN_VERSION,
            "aggregate": dataclasses.asdict(self.aggregate),
        }
        for field in ("clip", "compress", "bucket"):
            v = getattr(self, field)
            if v is not None:
                d[field] = dataclasses.asdict(v)
        d["schedule"] = dict(
            dataclasses.asdict(self.schedule),
            worker_axes=list(self.schedule.worker_axes),
        )
        if self.cohort is not None:
            d["cohort"] = self.cohort
        return d

    def to_json(self) -> str:
        """Canonical JSON name of the plan (stable key order)."""
        return json.dumps(self.to_dict(), sort_keys=True)

    @classmethod
    def from_dict(cls, d: dict) -> "ServerPlan":
        if "aggregate" not in d:
            raise PlanError("plan dict needs an 'aggregate' stage")
        version = d.get("version", PLAN_VERSION)  # pre-versioning docs = v1
        if version != PLAN_VERSION:
            raise PlanError(
                f"unsupported plan document version {version!r}; this "
                f"reader understands version {PLAN_VERSION} (and "
                "version-less documents, which are v1)"
            )
        unknown = set(d) - set(_SPEC_FIELDS) - {"cohort", "version"}
        if unknown:
            raise PlanError(
                f"unknown plan fields {sorted(unknown)}; have "
                f"{sorted(_SPEC_FIELDS)} + ['cohort', 'version']"
            )
        kw = {}
        for field, klass in _SPEC_FIELDS.items():
            if field in d and d[field] is not None:
                v = dict(d[field])
                if field == "schedule":
                    v["worker_axes"] = tuple(v.get("worker_axes", ()))
                kw[field] = klass(**v)
        if d.get("cohort") is not None:
            kw["cohort"] = int(d["cohort"])
        return cls(**kw)

    @classmethod
    def from_json(cls, s) -> "ServerPlan":
        try:
            d = json.loads(s) if isinstance(s, (str, bytes)) else dict(s)
        except (json.JSONDecodeError, TypeError) as e:
            raise PlanError(f"not a plan JSON document: {e}") from e
        return cls.from_dict(d)


# ---------------------------------------------------------------------------
# the compiled step
# ---------------------------------------------------------------------------

class ServerStep:
    """A compiled ServerPlan: ONE callable running the whole composition.

    ``step(msgs, mask=None, key=None, radius=None, base_specs=None)``:

      - ``msgs`` — (n, d) message matrix or worker-stacked pytree.
      - ``radius`` — per-call clip radius (e.g. ``step.radius(x_new, x)``
        for a ClipSpec(alpha) plan); None falls back to the plan's static
        ``ClipSpec(radius=...)``, or no clipping when the plan has no clip
        stage.
      - mesh builds additionally take ``base_specs`` (the unstacked grad
        PartitionSpecs) and run the configured collective schedule;
        ``with_factors=True`` returns ``(aggregate, clip factors)``;
        engine builds (mesh=None) run whole-message semantics through the
        fused dispatch-layer kernels.

    ``step.compress(key, x)`` applies the plan's compression stage (the
    identity when absent), ``step.aggregate(...)`` forces the unclipped
    form, ``step.radius(x_new, x_old)`` evaluates the data-dependent
    ClipSpec(alpha) radius (None when the plan does not clip).
    """

    def __init__(self, plan: ServerPlan, mesh=None):
        self.plan = plan
        self.mesh = mesh
        self.aggregator: Aggregator = plan.build_aggregator()
        self.compressor: Optional[Compressor] = plan.build_compressor()

    # -- stage helpers -------------------------------------------------------

    @property
    def clips(self) -> bool:
        return self.plan.clip is not None

    def radius(self, x_new, x_old):
        """lambda = alpha * ||x_new - x_old|| for a ClipSpec(alpha) plan;
        the static radius for ClipSpec(radius=); None when not clipping."""
        clip = self.plan.clip
        if clip is None:
            return None
        if clip.radius is not None:
            return jnp.float32(clip.radius)
        from ..core.clipping import marina_radius

        return marina_radius(x_new, x_old, clip.alpha)

    def compress(self, key, x):
        """Worker-side compression stage (identity when the plan has no
        compress stage) — vmap over per-worker keys/messages."""
        if self.compressor is None:
            return x
        return self.compressor(key, x)

    def aggregate(self, msgs, mask=None, key=None, base_specs=None):
        """The unclipped aggregation — Algorithm 1's full-gradient rounds
        aggregate raw gradients, so this bypasses even a static
        ``ClipSpec(radius=)``."""
        return self(msgs, mask=mask, key=key, radius=None,
                    base_specs=base_specs, _allow_static_clip=False)

    # -- the step ------------------------------------------------------------

    def __call__(self, msgs, mask=None, key=None, radius=None,
                 base_specs=None, with_factors=False,
                 _allow_static_clip=True):
        plan = self.plan
        if radius is None and _allow_static_clip and plan.clip is not None \
                and plan.clip.radius is not None:
            radius = jnp.float32(plan.clip.radius)
        if self.mesh is not None:
            from .mesh_exec import run_mesh_aggregate

            return run_mesh_aggregate(
                msgs, mask, key, mesh=self.mesh, agg=self.aggregator,
                spec=plan.schedule, base_specs=base_specs, radius=radius,
                with_factors=with_factors,
            )
        if base_specs is not None or with_factors:
            raise PlanError(
                "base_specs and with_factors are mesh-build arguments; this "
                "ServerStep was built with mesh=None"
            )
        if radius is None:
            return self.aggregator(msgs, mask=mask, key=key)
        return self.aggregator.clip_then_aggregate(
            msgs, radius, mask=mask, key=key
        )


def _total_elems(shapes) -> int:
    """Coordinate count of a message description (int, shape tuple,
    array-like, or a pytree of those)."""
    import numpy as np

    if isinstance(shapes, (int,)):
        return int(shapes)
    if hasattr(shapes, "shape"):
        return int(np.prod(shapes.shape, dtype=np.int64))
    if isinstance(shapes, (tuple, list)) and all(
        isinstance(x, int) for x in shapes
    ):
        return int(np.prod(shapes, dtype=np.int64)) if shapes else 0
    import jax

    leaves = jax.tree_util.tree_leaves(
        shapes,
        is_leaf=lambda x: hasattr(x, "shape")
        or (isinstance(x, (tuple, list)) and all(isinstance(i, int) for i in x)),
    )
    return int(sum(_total_elems(l) for l in leaves))
