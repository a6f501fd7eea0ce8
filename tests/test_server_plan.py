"""The ServerPlan API: validation, serialization, engine equivalence.

Pins the api_redesign contract:

  - invalid spec combos raise precise PlanError messages at construction
    (trim ratio, m_select on plain krum, pipelined x naive, cohort vs
    workers, rows vs mesh W) and superleaf-on-iterative warns;
  - to_json/from_json round-trips every stage and versions the document;
  - the legacy string knobs (``plan_from_legacy``, the "bucket_"
    make_aggregator prefix, config fields like ``aggregator=``/
    ``use_clipping=``) are GONE — a plan document is the only spelling;
  - ``robust_aggregate`` and the engine default plans are
    TRAJECTORY-BITWISE-EQUAL to the plan-built ServerStep — for the
    whole aggregator registry on both backends;
  - plan.estimate reuses the benchmark traffic models;
  - the CLI helpers build the same plan from flags and from --plan-json.
"""
import argparse
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    AggregatorSpec,
    BucketSpec,
    ClipSpec,
    CompressSpec,
    PLAN_VERSION,
    PlanError,
    PlanWarning,
    ScheduleSpec,
    ServerPlan,
)
from repro.core.aggregators import make_aggregator

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

def test_trim_ratio_out_of_range_raises():
    with pytest.raises(PlanError, match=r"trim_ratio must be in \[0, 0.5\)"):
        AggregatorSpec("trimmed_mean", trim_ratio=0.5)
    with pytest.raises(PlanError, match="trim_ratio"):
        ServerPlan(aggregate=AggregatorSpec("tm", trim_ratio=-0.1))


def test_cohort_exceeding_workers_raises():
    plan = ServerPlan(aggregate=AggregatorSpec("cm"), cohort=8)
    with pytest.raises(PlanError, match="cohort C=8 exceeds the 4"):
        plan.validate_workers(4)
    plan.validate_workers(8)  # boundary is fine


def test_pipelined_with_naive_placement_raises():
    with pytest.raises(PlanError, match="requires placement='sharded'"):
        ServerPlan(
            aggregate=AggregatorSpec("cm"),
            schedule=ScheduleSpec(placement="naive", blocks="pipelined"),
        )


def test_superleaf_on_iterative_rule_warns_block_partition():
    for rule in ("centered_clip", "rfa"):
        with pytest.warns(PlanWarning, match="block partition"):
            ServerPlan(
                aggregate=AggregatorSpec(rule),
                schedule=ScheduleSpec(placement="sharded",
                                      superleaf_elems=128),
            )
    # exact rules do not warn
    with warnings.catch_warnings():
        warnings.simplefilter("error", PlanWarning)
        ServerPlan(
            aggregate=AggregatorSpec("krum"),
            schedule=ScheduleSpec(placement="sharded", superleaf_elems=128),
        )


def test_worker_rows_vs_mesh_w_raises():
    from repro.launch.mesh import make_debug_mesh

    mesh = make_debug_mesh(1, 1)
    plan = ServerPlan(
        aggregate=AggregatorSpec("cm"),
        schedule=ScheduleSpec(placement="sharded"),
    )
    with jax.set_mesh(mesh):
        step = plan.build(mesh)
        with pytest.raises(PlanError, match="one row per worker"):
            step({"a": jnp.ones((2, 4))}, mask=jnp.ones(2, bool), key=KEY)


def test_misc_spec_validation():
    with pytest.raises(PlanError, match="exactly one of alpha"):
        ClipSpec()
    with pytest.raises(PlanError, match="exactly one of alpha"):
        ClipSpec(alpha=1.0, radius=2.0)
    with pytest.raises(PlanError, match="must be > 0"):
        ClipSpec(alpha=-1.0)
    with pytest.raises(PlanError, match="k >= 1"):
        CompressSpec(kind="rand_k", k=0)
    with pytest.raises(PlanError, match="0 < frac <= 1"):
        CompressSpec(kind="rand_fraction", frac=1.5)
    with pytest.raises(PlanError, match="bucket size s >= 2"):
        BucketSpec(s=1)
    with pytest.raises(PlanError, match="unknown aggregator rule"):
        AggregatorSpec("nope")
    with pytest.raises(PlanError, match="m_select is a multi_krum"):
        AggregatorSpec("krum", m_select=3)
    with pytest.raises(PlanError, match="unknown placement"):
        ScheduleSpec(placement="nope")
    with pytest.raises(PlanError, match="unknown schedule"):
        ScheduleSpec(blocks="nope")
    with pytest.raises(PlanError, match="superleaf_elems"):
        ScheduleSpec(superleaf_elems=-1)
    with pytest.raises(PlanError, match="unknown backend"):
        ScheduleSpec(backend="cuda")
    with pytest.raises(PlanError, match="needs a mesh"):
        ServerPlan(
            aggregate=AggregatorSpec("cm"),
            schedule=ScheduleSpec(placement="sharded"),
        ).build()


def test_rule_aliases_normalize():
    assert AggregatorSpec("tm").rule == "trimmed_mean"
    assert AggregatorSpec("cclip").rule == "centered_clip"
    assert AggregatorSpec("gm").rule == "rfa"
    assert AggregatorSpec("geometric_median").rule == "rfa"


# ---------------------------------------------------------------------------
# serialization
# ---------------------------------------------------------------------------

def _full_plan():
    return ServerPlan(
        aggregate=AggregatorSpec("multi_krum", byz_bound=2, m_select=3),
        clip=ClipSpec(alpha=2.0),
        compress=CompressSpec(kind="rand_fraction", frac=0.25),
        bucket=BucketSpec(s=3),
        schedule=ScheduleSpec(placement="sharded", blocks="pipelined",
                              superleaf_elems=4096, backend="pallas",
                              worker_axes=("pod",)),
        cohort=4,
    )


def test_json_round_trip_every_stage():
    plan = _full_plan()
    assert ServerPlan.from_json(plan.to_json()) == plan
    # minimal plan too
    minimal = ServerPlan(aggregate=AggregatorSpec("cm"))
    assert ServerPlan.from_json(minimal.to_json()) == minimal
    # canonical: same plan -> same string
    assert plan.to_json() == _full_plan().to_json()


def test_from_json_rejects_garbage():
    with pytest.raises(PlanError):
        ServerPlan.from_json("not json at all {{{")
    with pytest.raises(PlanError, match="aggregate"):
        ServerPlan.from_json("{}")
    with pytest.raises(PlanError, match="unknown plan fields"):
        ServerPlan.from_json('{"aggregate": {"rule": "cm"}, "wat": 1}')


def test_plan_json_is_versioned():
    import json

    doc = json.loads(_full_plan().to_json())
    assert doc["version"] == PLAN_VERSION
    # pre-versioning documents (no "version" key) parse as v1
    del doc["version"]
    assert ServerPlan.from_json(json.dumps(doc)) == _full_plan()
    # unknown versions are rejected, not silently reinterpreted
    doc["version"] = PLAN_VERSION + 1
    with pytest.raises(PlanError, match="version"):
        ServerPlan.from_json(json.dumps(doc))


# ---------------------------------------------------------------------------
# estimate
# ---------------------------------------------------------------------------

def test_estimate_reuses_traffic_models():
    from benchmarks.bench_kernels import (
        traffic_model,
        traffic_model_iterative,
        traffic_model_krum,
    )

    n, d = 16, 4096
    est = ServerPlan(aggregate=AggregatorSpec("krum")).estimate(
        d, n_workers=n
    )
    assert est["server_step"] == traffic_model_krum(n, d)
    assert "apply_pass" in est
    est = ServerPlan(aggregate=AggregatorSpec("cm")).estimate(
        d, n_workers=n
    )
    assert est["server_step"] == traffic_model(n, d)
    est = ServerPlan(aggregate=AggregatorSpec("cclip")).estimate(
        d, n_workers=n
    )
    assert est["server_step"] == traffic_model_iterative(n, d, 5)
    # shapes may be a pytree; sharded placement adds the pipeline model
    with pytest.warns(PlanWarning):
        plan = ServerPlan(
            aggregate=AggregatorSpec("rfa"),
            schedule=ScheduleSpec(placement="sharded",
                                  superleaf_elems=1024),
        )
    est = plan.estimate({"a": (8, 256), "b": (2048,)}, n_workers=4)
    assert est["d"] == 8 * 256 + 2048
    assert est["pipeline"]["n_blocks"] == 4
    assert est["server_step"] == traffic_model_iterative(4, est["d"], 8)
    with pytest.raises(PlanError, match="worker count"):
        ServerPlan(aggregate=AggregatorSpec("cm")).estimate(128)


# ---------------------------------------------------------------------------
# legacy knobs are gone
# ---------------------------------------------------------------------------

def test_legacy_spellings_are_removed():
    """The deprecation window is over: ``plan_from_legacy``, the
    ``bucket_<rule>`` make_aggregator prefix and the string-knob config
    fields no longer exist — a ServerPlan document is the only spelling
    (see the README migration table)."""
    import repro.api

    assert not hasattr(repro.api, "plan_from_legacy")
    with pytest.raises(ValueError, match="unknown aggregator"):
        make_aggregator("bucket_cm", backend="jnp")
    from repro.core.marina_pp import MarinaPPConfig
    from repro.launch.train import ByzTrainConfig

    with pytest.raises(TypeError):
        MarinaPPConfig(gamma=0.5, p=0.2, C=4, C_hat=20, aggregator="cm")
    with pytest.raises(TypeError):
        MarinaPPConfig(gamma=0.5, p=0.2, C=4, C_hat=20, use_clipping=False)
    with pytest.raises(TypeError):
        ByzTrainConfig(agg_schedule="naive")


def test_heuristic_static_clip_radius_applies_from_step_zero():
    """The step-0 warmup override (lambda -> +inf) exists because the
    data-dependent alpha radius is 0 before the first move; a static
    ClipSpec(radius=) is user-chosen and must clip step 0 too."""
    from repro.core.heuristic import ClippedPPConfig, ClippedPPMomentum
    from repro.core.problems import logistic_problem

    prob = logistic_problem(
        jax.random.PRNGKey(0), n_clients=8, n_good=8, m=40, dim=20,
        homogeneous=False,
    )
    radius = 1e-3
    plan = ServerPlan(aggregate=AggregatorSpec("cm"),
                      clip=ClipSpec(radius=radius),
                      bucket=BucketSpec(2),
                      schedule=ScheduleSpec(backend="jnp"))
    alg = ClippedPPMomentum(prob, ClippedPPConfig(gamma=0.1, C=8, plan=plan))
    s0 = alg.init()
    s1 = alg.step(s0)
    # every clipped message coordinate is <= radius in magnitude, and CM of
    # bucket means stays in their hull, so ||g1 - g0|| <= sqrt(d) * radius;
    # the old warmup override would let the raw (unclipped) diffs through
    delta = float(jnp.linalg.norm(s1.g - s0.g))
    assert delta <= np.sqrt(prob.dim) * radius * 1.01, delta
    assert delta > 0.0


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_robust_aggregate_vs_plan_registry_trajectory_bitwise(backend):
    """Acceptance gate: for EVERY registry rule (bucketed and not) the
    ``robust_aggregate`` functional entry point and the plan-built
    ServerStep produce bitwise-equal multi-step g += Agg(msgs(g))
    trajectories (the naive placement runs in-process; the
    sharded/pipelined placements are covered by the 8-device subprocess
    tests, which route through the same plan)."""
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.train import ByzTrainConfig, resolve_plan, robust_aggregate

    mesh = make_debug_mesh(1, 1)
    rng = np.random.RandomState(0)
    base = {
        "a": jnp.asarray(rng.randn(6, 3, 8).astype(np.float32)),
        "b": {"c": jnp.asarray(rng.randn(6, 17).astype(np.float32))},
    }
    mask = jnp.asarray([1, 1, 0, 1, 1, 1], bool)
    radius = jnp.float32(2.0)

    with jax.set_mesh(mesh):
        for name, bucket_s in (("cm", 0), ("tm", 0), ("mean", 0),
                               ("cclip", 0), ("rfa", 0), ("krum", 0),
                               ("multi_krum", 0), ("cm", 2), ("krum", 2),
                               ("rfa", 2)):
            plan = ServerPlan(
                aggregate=AggregatorSpec(name, byz_bound=1),
                bucket=BucketSpec(s=bucket_s) if bucket_s else None,
                schedule=ScheduleSpec(placement="naive", backend=backend),
            )
            cfg = ByzTrainConfig.from_plan(plan, n_byz=1)
            step = resolve_plan(cfg).build(mesh)

            g_legacy = jax.tree_util.tree_map(
                lambda l: jnp.zeros(l.shape[1:]), base
            )
            g_plan = g_legacy
            for t in range(4):
                k = jax.random.fold_in(KEY, t)
                msgs_l = jax.tree_util.tree_map(
                    lambda b, g: b + 0.3 * g[None], base, g_legacy
                )
                msgs_p = jax.tree_util.tree_map(
                    lambda b, g: b + 0.3 * g[None], base, g_plan
                )
                a_l = robust_aggregate(msgs_l, mask, k, mesh=mesh, cfg=cfg,
                                       radius=radius)
                a_p = step(msgs_p, mask=mask, key=k, radius=radius)
                g_legacy = jax.tree_util.tree_map(
                    lambda a, b: a + b, g_legacy, a_l
                )
                g_plan = jax.tree_util.tree_map(
                    lambda a, b: a + b, g_plan, a_p
                )
            for la, lb in zip(jax.tree_util.tree_leaves(g_legacy),
                              jax.tree_util.tree_leaves(g_plan)):
                np.testing.assert_array_equal(
                    np.asarray(la), np.asarray(lb),
                    err_msg=f"{name} {backend}",
                )


def test_engine_default_plan_vs_explicit_trajectory_bitwise():
    """``MarinaPPConfig(plan=None)`` resolves to the paper's documented
    default composition — CM over Bucketing(2), clip at alpha=1.0 — and
    produces a loss trajectory bitwise-equal to spelling that plan out."""
    from repro.core.marina_pp import ByzVRMarinaPP, MarinaPPConfig
    from repro.core.problems import logistic_problem

    prob = logistic_problem(
        jax.random.PRNGKey(0), n_clients=12, n_good=10, m=40, dim=20,
        homogeneous=False,
    )

    def trace(cfg):
        alg = ByzVRMarinaPP(prob, cfg)
        _, metrics = jax.jit(lambda s: alg.run(12, s))(alg.init())
        return np.asarray(metrics["loss"])

    implicit = trace(MarinaPPConfig(
        gamma=0.05, p=0.25, C=4, C_hat=12, batch=16, attack="shb",
    ))
    plan = ServerPlan(aggregate=AggregatorSpec("cm"),
                      clip=ClipSpec(alpha=1.0), bucket=BucketSpec(2))
    explicit = trace(MarinaPPConfig(
        gamma=0.05, p=0.25, C=4, C_hat=12, batch=16, attack="shb",
        plan=plan,
    ))
    np.testing.assert_array_equal(implicit, explicit)
    assert np.isfinite(explicit).all()


def test_byz_train_config_from_plan_is_the_source_of_truth():
    from repro.launch.train import ByzTrainConfig, resolve_plan

    plan = _full_plan()
    cfg = ByzTrainConfig.from_plan(plan, gamma=0.5, n_byz=2, attack="gauss")
    assert cfg.plan is plan
    assert resolve_plan(cfg) is plan  # no translation, no mirror fields
    assert cfg.gamma == 0.5 and cfg.n_byz == 2 and cfg.attack == "gauss"
    # the default composition is documented: sharded CM with byz_bound
    # from n_byz and the cohort from C
    default = resolve_plan(ByzTrainConfig(n_byz=3, C=5))
    assert default.aggregate.rule == "cm"
    assert default.aggregate.byz_bound == 3
    assert default.schedule.placement == "sharded"
    assert default.clip == ClipSpec(alpha=2.0)
    assert default.cohort == 5


# ---------------------------------------------------------------------------
# CLI helpers
# ---------------------------------------------------------------------------

def _parse(argv):
    from repro.launch.cli import add_plan_args, plan_from_args

    ap = argparse.ArgumentParser()
    add_plan_args(ap)
    return plan_from_args(ap.parse_args(argv), byz_bound=1, clip_alpha=2.0)


def test_cli_flags_build_plan():
    plan = _parse(["--aggregator", "krum", "--bucket-s", "2",
                   "--agg-schedule", "sharded", "--schedule", "pipelined",
                   "--superleaf-elems", "64", "--backend", "pallas"])
    assert plan.aggregate.rule == "krum"
    assert plan.aggregate.byz_bound == 1
    assert plan.bucket == BucketSpec(2)
    assert plan.clip == ClipSpec(alpha=2.0)
    assert plan.schedule == ScheduleSpec(
        placement="sharded", blocks="pipelined", superleaf_elems=64,
        backend="pallas",
    )


def test_cli_plan_json_round_trip(tmp_path):
    want = _full_plan()
    # inline JSON
    assert _parse(["--plan-json", want.to_json()]) == want
    # and from a file
    p = tmp_path / "plan.json"
    p.write_text(want.to_json())
    assert _parse(["--plan-json", str(p)]) == want


# ---------------------------------------------------------------------------
# serving endpoint
# ---------------------------------------------------------------------------

def test_scoring_endpoint_matches_plan_step_and_flags_outliers():
    from repro.launch.serve import make_scoring_step

    plan = ServerPlan(aggregate=AggregatorSpec("krum", byz_bound=2),
                      clip=ClipSpec(radius=5.0))
    scoring = jax.jit(make_scoring_step(plan))
    rng = np.random.RandomState(0)
    xs = rng.randn(3, 8, 64).astype(np.float32)
    xs[:, 6:, :] *= 100.0  # trailing 2 clients are byzantine
    out = scoring(jnp.asarray(xs), key=KEY)
    assert out["aggregate"].shape == (3, 64)
    assert out["distance"].shape == (3, 8)
    # per-request aggregate == the plan's ServerStep on that request
    # (static ClipSpec(radius) applied by both)
    step = plan.build()
    keys = jax.random.split(KEY, 3)
    for b in range(3):
        want = step(jnp.asarray(xs[b]), mask=jnp.ones(8, bool),
                    key=keys[b])
        np.testing.assert_array_equal(
            np.asarray(out["aggregate"][b]),
            np.asarray(want.astype(jnp.float32)),
        )
    d = np.asarray(out["distance"])
    assert d[:, 6:].min() > d[:, :6].max(), "byz rows must score as outliers"
    cf = np.asarray(out["clip_factor"])
    assert (cf[:, 6:] < 0.2).all() and (cf <= 1.0 + 1e-6).all()


def test_scoring_endpoint_respects_participation_mask():
    from repro.launch.serve import make_scoring_step

    plan = ServerPlan(aggregate=AggregatorSpec("cm"))
    scoring = make_scoring_step(plan)
    rng = np.random.RandomState(1)
    xs = jnp.asarray(rng.randn(2, 6, 16).astype(np.float32))
    mask = jnp.asarray([[1, 1, 1, 0, 0, 0], [0, 0, 0, 1, 1, 1]], bool)
    out = scoring(xs, batch_mask=mask, key=KEY)
    for b in range(2):
        want = np.median(np.asarray(xs[b])[np.asarray(mask[b])], axis=0)
        np.testing.assert_allclose(np.asarray(out["aggregate"][b]), want,
                                   atol=1e-6)


def test_scoring_endpoint_rejects_unservable_plans():
    from repro.launch.serve import make_scoring_step

    with pytest.raises(PlanError, match="iterate pair"):
        make_scoring_step(ServerPlan(aggregate=AggregatorSpec("cm"),
                                     clip=ClipSpec(alpha=1.0)))
    with pytest.raises(PlanError, match="placement='naive'"):
        make_scoring_step(ServerPlan(
            aggregate=AggregatorSpec("cm"),
            schedule=ScheduleSpec(placement="sharded"),
        ))


def test_every_cli_shares_the_plan_flags():
    """The satellite contract: launch/train.py, the example trainer and
    the serving scorer declare the plan flags through ONE helper
    (launch/cli.add_plan_args) — none re-declares them locally, so a new
    spec field lands in every CLI automatically."""
    import pathlib

    root = pathlib.Path(__file__).resolve().parent.parent
    sources = {
        "train": root / "src" / "repro" / "launch" / "train.py",
        "serve": root / "src" / "repro" / "launch" / "serve.py",
        "example": root / "examples" / "train_marina_pp.py",
    }
    for name, path in sources.items():
        src = path.read_text()
        assert "add_plan_args(" in src, f"{name} must use launch.cli"
        for flag in ("--backend", "--schedule", "--superleaf-elems",
                     "--aggregator", "--agg-schedule", "--plan-json"):
            assert f'"{flag}"' not in src, (
                f"{name} re-declares {flag} instead of using "
                "launch.cli.add_plan_args"
            )
