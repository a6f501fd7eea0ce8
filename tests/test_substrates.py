"""Substrate tests: optimizers, schedules, data pipeline, checkpointing,
sharding rules, theory/analytic models."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore, save
from repro.data.federated import dirichlet_split, federated_shards
from repro.data.pipeline import TokenStream, synthetic_batch
from repro.models.model import ModelConfig
from repro.optim import adamw, constant, cosine_decay, momentum, sgd, warmup_cosine


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("opt", [sgd(), momentum(0.9), adamw()], ids=lambda o: o.name)
def test_optimizer_reduces_quadratic(opt):
    params = {"w": jnp.asarray([3.0, -2.0, 1.0])}
    state = opt.init(params)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    for _ in range(100):
        g = jax.grad(loss)(params)
        params, state = opt.apply(params, g, state, 0.05)
    assert float(loss(params)) < 1e-2


def test_schedules():
    assert float(constant(0.1)(5)) == pytest.approx(0.1)
    cd = cosine_decay(1.0, 100, final_frac=0.1)
    assert float(cd(0)) == pytest.approx(1.0)
    assert float(cd(100)) == pytest.approx(0.1, abs=1e-6)
    wc = warmup_cosine(1.0, warmup=10, total_steps=110)
    assert float(wc(0)) == pytest.approx(0.0)
    assert float(wc(10)) == pytest.approx(1.0)
    assert float(wc(5)) == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def test_token_stream_deterministic_and_sharded():
    cfg = ModelConfig(name="t", n_layers=2, d_model=64, n_heads=2,
                      n_kv_heads=2, d_ff=128, vocab=100)
    it1 = iter(TokenStream(cfg, batch=2, seq=8, seed=3))
    it2 = iter(TokenStream(cfg, batch=2, seq=8, seed=3))
    b1, b2 = next(it1), next(it2)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    it3 = iter(TokenStream(cfg, batch=2, seq=8, seed=3, shard_id=1, num_shards=4))
    b3 = next(it3)
    assert not np.array_equal(np.asarray(b1["tokens"]), np.asarray(b3["tokens"]))
    assert int(b1["tokens"].max()) < 100


def test_synthetic_batch_kinds():
    base = dict(n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, d_ff=128, vocab=50)
    cfg = ModelConfig(name="a", **base, input_kind="frames", frame_dim=16)
    b = synthetic_batch(jax.random.PRNGKey(0), cfg, 2, 8)
    assert b["frames"].shape == (2, 8, 16) and b["targets"].shape == (2, 8)
    cfg = ModelConfig(name="v", **base, input_kind="tokens+vision", n_vision_tokens=5)
    b = synthetic_batch(jax.random.PRNGKey(0), cfg, 2, 8)
    assert b["vision"].shape == (2, 5, 64)


def test_federated_shards_equal_sizes():
    f = np.random.randn(103, 7).astype(np.float32)
    l = (np.random.rand(103) > 0.5).astype(np.float32)
    fs, ls = federated_shards(f, l, 10)
    assert fs.shape == (10, 10, 7) and ls.shape == (10, 10)


def test_dirichlet_split_heterogeneous():
    rng = np.random.RandomState(0)
    f = rng.randn(1000, 3).astype(np.float32)
    l = rng.randint(0, 10, 1000)
    fs, ls = dirichlet_split(f, l, n_clients=10, alpha=0.1, seed=0)
    assert fs.shape == (10, 100, 3)
    # heterogeneity: per-client label histograms differ materially
    hists = np.stack([np.bincount(ls[i].astype(int), minlength=10) for i in range(10)])
    assert hists.std(axis=0).mean() > 2.0


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_with_bf16():
    tree = {
        "w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "nested": {"b": jnp.ones((4,), jnp.bfloat16) * 1.5,
                   "i": jnp.arange(3, dtype=jnp.int32)},
    }
    with tempfile.TemporaryDirectory() as d:
        save(d, 7, tree)
        assert latest_step(d) == 7
        template = jax.tree_util.tree_map(jnp.zeros_like, tree)
        back = restore(d, 7, template)
        np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(tree["w"]))
        assert back["nested"]["b"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(back["nested"]["b"], np.float32),
            np.asarray(tree["nested"]["b"], np.float32),
        )
    assert latest_step("/nonexistent/dir") is None


def test_truncated_checkpoint_is_skipped_not_resumed():
    """Regression: a writer killed mid-npz used to leave a truncated
    ``step_<k>.npz`` that ``latest_step`` happily returned and
    ``restore`` crashed on.  Writes are now atomic AND the reader
    verifies candidates newest-first, so resume lands on the newest
    COMPLETE step."""
    from repro.checkpoint import verify_step

    tree = {"w": jnp.arange(8, dtype=jnp.float32)}
    template = jax.tree_util.tree_map(jnp.zeros_like, tree)
    with tempfile.TemporaryDirectory() as d:
        save(d, 1, tree)
        save(d, 2, tree)
        # simulate the pre-fix torn write: step 2's archive loses its
        # tail (the zip central directory) after publication
        npz2 = os.path.join(d, "step_2.npz")
        blob = open(npz2, "rb").read()
        with open(npz2, "wb") as f:
            f.write(blob[: len(blob) // 2])
        assert not verify_step(d, 2) and verify_step(d, 1)
        assert latest_step(d) == 1  # damaged newest is skipped
        back = restore(d, 1, template)
        np.testing.assert_array_equal(np.asarray(back["w"]),
                                      np.asarray(tree["w"]))
        # unverified listing still sees the damaged step (debugging)
        assert latest_step(d, verify=False) == 2
        # leftover .tmp files from a kill mid-write never count as steps
        open(os.path.join(d, "step_9.npz.tmp.npz"), "wb").close()
        assert latest_step(d) == 1


def test_checkpoint_save_publishes_atomically():
    """No partially-written step is ever visible under the final name:
    after save() the directory holds exactly the step files, no temps,
    and the manifest lands before the npz (the npz IS the publication
    marker latest_step keys on)."""
    tree = {"w": jnp.ones((3,), jnp.float32)}
    with tempfile.TemporaryDirectory() as d:
        save(d, 4, tree)
        names = sorted(os.listdir(d))
        assert names == ["step_4.json", "step_4.npz"]
        assert os.path.getmtime(os.path.join(d, "step_4.json")) <= \
            os.path.getmtime(os.path.join(d, "step_4.npz"))


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_param_specs_tp_and_fsdp():
    import jax as _jax
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_smoke_config
    from repro.models.model import init_params
    from repro.sharding.rules import param_specs

    cfg = get_smoke_config("minitron_8b")
    shapes = _jax.eval_shape(lambda k: init_params(k, cfg), _jax.random.PRNGKey(0))
    try:
        mesh = _jax.sharding.AbstractMesh((4, 4), ("data", "model"))
    except TypeError:  # jax < 0.5: AbstractMesh takes ((name, size), ...)
        mesh = _jax.sharding.AbstractMesh((("data", 4), ("model", 4)))
    specs_tp = param_specs(mesh, cfg, shapes, mode="tp")
    specs_fs = param_specs(mesh, cfg, shapes, mode="fsdp_tp")
    flat_tp = jax.tree_util.tree_leaves(specs_tp, is_leaf=lambda x: isinstance(x, P))
    flat_fs = jax.tree_util.tree_leaves(specs_fs, is_leaf=lambda x: isinstance(x, P))
    # fsdp mode must introduce "data" sharding on some kernels, tp must not
    assert not any("data" in str(s) for s in flat_tp)
    assert any("data" in str(s) for s in flat_fs)
    assert any("model" in str(s) for s in flat_tp)
    # every spec rank matches its leaf rank
    for spec, leaf in zip(
        flat_tp, jax.tree_util.tree_leaves(shapes)
    ):
        assert len(spec) <= len(leaf.shape)


def test_analytic_flops_sane():
    from benchmarks.analytic import step_flops
    from repro.configs import get_config

    cfg = get_config("deepseek_7b")
    fl = step_flops(cfg, seq=4096, batch=256, mode="train")
    # 6*N*D*2(sarah)*(4/3 remat) band: N=7e9, D=1.05e6 tokens
    approx = 6 * 7e9 * 4096 * 256 * 2 * 4 / 3
    assert 0.3 * approx < fl["total"] < 3 * approx
    dec = step_flops(cfg, seq=32768, batch=128, mode="decode")
    assert dec["total"] < fl["total"] / 1e3


# ---------------------------------------------------------------------------
# persistent compilation cache of the command-line entry points
# ---------------------------------------------------------------------------

_CACHE_SCRIPT = """
import jax, jax.numpy as jnp
from repro.launch.cache import enable_compile_cache
hits = []
jax.monitoring.register_event_listener(
    lambda event, **kw: hits.append(event)
    if event == "/jax/compilation_cache/cache_hits" else None)
print("DIR", enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.ones(8)).block_until_ready()
print("HITS", len(hits))
"""


def _cache_run(env):
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_SCRIPT], env=env, cwd=root,
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = dict(l.split(" ", 1) for l in r.stdout.splitlines()
                 if l.startswith(("DIR ", "HITS ")))
    return lines["DIR"], int(lines["HITS"])


def test_compile_cache_uses_env_dir_and_hits_on_second_run():
    """JAX_COMPILATION_CACHE_DIR, when set, is where entries land;
    unset, the cache is the fixed .jax_cache/ at the checkout root.
    Either way a second process finds the first one's entries."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    base["PYTHONPATH"] = os.path.join(root, "src")
    with tempfile.TemporaryDirectory() as d:
        env = dict(base, JAX_COMPILATION_CACHE_DIR=d)
        where, hits = _cache_run(env)
        assert where == d and hits == 0
        assert os.listdir(d), "no cache entry written"
        _, hits = _cache_run(env)
        assert hits >= 1
    where, _ = _cache_run(base)
    assert where == os.path.join(root, ".jax_cache")
    _, hits = _cache_run(base)
    assert hits >= 1
