"""CPU rehearsal of ``chip_smoke.py`` and the one-process-per-chip rules.

The phases run here at a tiny size on the CPU, with the Pallas kernels
interpreted, against the float64 oracle -- the same comparison the chip
run makes. The script itself must refuse to run off the chip; the
compile cache must live where ``JAX_COMPILATION_CACHE_DIR`` says, else
inside the checkout; and no measurement path may report a CPU number
where a chip was expected.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

#: (events or baskets, Config overrides): tiny shapes, interpreted
#: kernels; the dense capacity derives from the data instead of the
#: 59k-item catalog.
TINY = {
    "dense": (12_000, {"num_items": 0, "pallas": "on"}),
    "dense-fused": (300, {"num_items": 0, "pallas": "on"}),
    "sparse": (60_000, {"pallas": "on"}),
    "sparse-fused": (60_000, {"pallas": "on"}),
    chip_smoke.SHARDED: (60_000, {"pallas": "on"}),
}


@pytest.mark.parametrize("phase", list(TINY))
def test_phase_matches_oracle_on_cpu(phase):
    size, overrides = TINY[phase]
    got = chip_smoke.run_phase(phase, size, **overrides)
    ref = chip_smoke.oracle_reference(chip_smoke.WORKLOAD[phase], size)
    ok, detail = chip_smoke.compare(got, ref)
    assert ok, detail
    assert got["windows"] >= 1 and got["pairs"] > 0
    assert got["interpret"] and got["pallas"]
    assert got["fused"] == (chip_smoke.SETTINGS[phase].get(
        "fused_window") == "on")
    if phase == chip_smoke.SHARDED:
        assert len(got["shard_devices"]) == 4


def test_compare_flags_a_wrong_id_and_a_wrong_counter():
    ref = {"counters": dict.fromkeys(chip_smoke.EXACT_COUNTERS, 5),
           "latest": {1: [(2, 9.0), (3, 4.0), (4, 1.0)]}}
    good = {"counters": dict(ref["counters"]),
            "latest": {1: [(2, 9.0 * (1 + 5e-5)), (3, 4.0), (5, 1.0)]}}
    # The last slot may near-tie the unseen (K+1)th score: exempt.
    assert chip_smoke.compare(good, ref)[0]
    wrong_id = dict(good, latest={1: [(3, 9.0), (2, 4.0), (4, 1.0)]})
    assert not chip_smoke.compare(wrong_id, ref)[0]
    wrong_count = dict(good, counters=dict(
        ref["counters"], RowSumProcessWindowRowSum=6))
    assert not chip_smoke.compare(wrong_count, ref)[0]


def _run_smoke(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_off_the_chip():
    r = _run_smoke(REPO, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "found no TPU" in r.stderr


def test_refuses_outside_the_checkout(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run_smoke(str(tmp_path), {"JAX_PLATFORMS": "cpu",
                                   "PYTHONPATH": ""})
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


# -- compile cache -----------------------------------------------------------

_CACHE_PROBE = (
    "import jax, jax.numpy as jnp, json, os;"
    "from tpu_cooccurrence.xla_cache import enable_compilation_cache;"
    "d = enable_compilation_cache();"
    "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready();"
    "print(json.dumps({'dir': d}))")


def _cache_probe(env):
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE], cwd=REPO,
                       env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])["dir"]


def test_compile_cache_env_dir_is_the_only_one(tmp_path):
    where = tmp_path / "cc"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(where))
    assert _cache_probe(env) == str(where)
    assert any(where.iterdir()), "the compile landed in the env dir"


def test_compile_cache_default_is_inside_the_checkout():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    d = _cache_probe(env)
    assert d.startswith(os.path.join(REPO, ".xla_cache") + os.sep)


# -- bench.py: no CPU number where a chip was expected ----------------------

def _bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("probed,child_line", [
    ("cpu", None),      # no accelerator: fail, start no child
    (None, None),       # probe hung or crashed: fail
    ("tpu", None),      # the chip child failed: fail, no CPU rerun
])
def test_bench_fails_when_the_chip_is_not_reached(monkeypatch, capsys,
                                                  probed, child_line):
    bench = _bench()
    children = []
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(bench, "probe_backend", lambda timeout_s: probed)
    monkeypatch.setattr(bench, "_run_child", lambda env, deadline: (
        children.append(env) or child_line))
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    assert bench.main() == 1
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["platform"] == "error"
    assert "cpu-fallback" not in out
    # Only a probed chip gets a child, and that child expects the chip.
    assert [c.get("BENCH_EXPECT_ACCEL") for c in children] == (
        ["1"] if probed == "tpu" else [])
    assert all(c.get("JAX_PLATFORMS") != "cpu" for c in children)


def test_bench_cpu_run_only_when_asked(monkeypatch, capsys):
    bench = _bench()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(bench, "probe_backend", lambda timeout_s: (
        pytest.fail("a CPU run asked for needs no probe")))
    monkeypatch.setattr(bench, "_run_child", lambda env, deadline: (
        '{"value": 1.0, "platform": "cpu"}'))
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    assert bench.main() == 0
    assert json.loads(capsys.readouterr().out)["platform"] == "cpu"


def test_bench_measure_child_refuses_cpu_when_chip_expected(monkeypatch):
    bench = _bench()
    monkeypatch.setenv("BENCH_EXPECT_ACCEL", "1")
    assert bench.measure() == 1


# -- one process per chip ----------------------------------------------------

def test_gang_refuses_workers_that_share_chips():
    from tpu_cooccurrence.robustness.gang import check_one_process_per_chip

    with pytest.raises(ValueError, match="each would claim every local"):
        check_one_process_per_chip(2, {})
    check_one_process_per_chip(2, {"JAX_PLATFORMS": "cpu"})
    check_one_process_per_chip(1, {})


def _parent_probe(argv, patch):
    """Run cli.main(argv) in a fresh process with ``spawned`` standing
    in for the spawner (installed by ``patch``); it prints how many JAX
    backends the parent had initialized when it would have spawned."""
    code = (
        "import sys, tpu_cooccurrence.cli as cli\n"
        "def spawned(*a, **k):\n"
        "    from jax._src import xla_bridge\n"
        "    print('BACKENDS', len(xla_bridge._backends))\n"
        "    return 0\n"
        f"{patch}\n"
        f"sys.exit(cli.main({argv!r}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("mode", ["supervisor", "gang"])
def test_supervising_parent_starts_no_backend(tmp_path, mode):
    data = tmp_path / "x.csv"
    rng = np.random.default_rng(0)
    data.write_text("".join(f"{u},{i},{t}\n" for u, i, t in zip(
        rng.integers(0, 20, 200), rng.integers(0, 30, 200), range(200))))
    argv = ["-i", str(data), "-ws", "50", "-s", "0xC0FFEE"]
    if mode == "supervisor":
        argv += ["--restart-on-failure", "1"]
        patch = ("import tpu_cooccurrence.supervisor as s; "
                 "s.supervise = spawned")
    else:
        argv += ["--backend", "sharded", "--num-shards", "2",
                 "--num-items", "32", "--gang-workers", "2"]
        patch = ("import tpu_cooccurrence.robustness.gang as g; "
                 "g.GangSupervisor.run = spawned")
    r = _parent_probe(argv, patch)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "BACKENDS 0" in r.stdout


def test_gang_cli_refuses_before_any_worker_starts(tmp_path, monkeypatch):
    from tpu_cooccurrence import cli
    from tpu_cooccurrence.robustness import gang

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(gang.GangSupervisor, "run", lambda self: (
        pytest.fail("no worker may start")))
    data = tmp_path / "x.csv"
    data.write_text("1,2,3\n")
    assert cli.main(["-i", str(data), "-ws", "50", "--backend", "sharded",
                     "--num-shards", "2", "--num-items", "32",
                     "--gang-workers", "2"]) == 78


# -- what the chip showed ----------------------------------------------------

def test_packed_ids_are_normal_floats():
    """TPUs flush denormal floats to zero even when only moving them:
    every id lane of a packed result block must be a normal float."""
    import jax.numpy as jnp

    from tpu_cooccurrence.state.results import pack_ids, unpack_ids

    ids = np.concatenate([np.arange(0, 5000), [2**23 - 1, 2**23, 2**24,
                                               2**31 - 2**24 - 1]])
    lanes = np.asarray(pack_ids(jnp.asarray(ids, jnp.int32)))
    assert (np.abs(lanes) >= np.finfo(np.float32).tiny).all()
    np.testing.assert_array_equal(unpack_ids(lanes), ids)


@pytest.mark.parametrize("lo,hi", [(-0.2, 0.2), (-0.999, -0.25),
                                   (0.25, 1e6)])
def test_log1p_f32_is_accurate(lo, hi):
    """The LLR's log1p uses only correctly rounded float32 arithmetic
    (TPU's log/log1p measured at up to 3.7e-4 relative error)."""
    import jax
    import jax.numpy as jnp

    from tpu_cooccurrence.ops.llr import log1p_f32

    x = np.linspace(lo, hi, 20_001, dtype=np.float32)
    got = np.asarray(jax.jit(log1p_f32)(jnp.asarray(x)), np.float64)
    want = np.log1p(x.astype(np.float64))
    rel = np.abs(got - want) / np.maximum(np.abs(want), 1e-30)
    assert rel.max() < 1e-6
