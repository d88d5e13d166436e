"""chip_smoke.py's phases, rehearsed on the CPU at a tiny size.

The script's real run needs a TPU (`python chip_smoke.py` on the chip);
what can rot without one is everything else — the SQL, the reference
computations, the asserts, the exit codes — and that runs here, in
process, through the script's own ``--rehearse`` path, which never reports
a ``tpu`` it did not run on.  Plus the three rules of the compile cache's
placement, and the refusals: no result without a TPU, none after a phase
raised, none when a query fell back.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

from ksql_tpu.common import faults  # noqa: E402
from ksql_tpu.runtime import compile_cache  # noqa: E402

SEED = 11
PLATFORM = "cpu"


def _last_json(stdout: str):
    lines = [x for x in stdout.splitlines() if x.strip()]
    return json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None


# ------------------------------------------------------------ the phases
def test_phase_served_config1_over_http():
    facts = chip_smoke.phase_served(chip_smoke.TINY, SEED, PLATFORM)
    assert facts["live_entries"] >= chip_smoke.TINY.min_live


@pytest.mark.parametrize("config", [
    "config2_hopping_multi_udaf",
    "config3_stream_table_join",
    "config4_stream_stream_join",
    "config5_session",
])
def test_phase_steps_equal_the_oracle_twin(config):
    done = chip_smoke.phase_steps(chip_smoke.TINY, SEED, PLATFORM, only=config)
    assert list(done) == [config] and done[config]["compiles"] > 0


def test_phase_taps_fused_kernel():
    facts = chip_smoke.phase_taps(chip_smoke.TINY, SEED, PLATFORM)
    assert facts["kernel_degraded"] is None and facts["kernel_evals"] > 0
    assert facts["delivered"] == chip_smoke.TINY.tap_events


def test_phase_cache_second_build_hits():
    # tiny programs compile in under the floor the suite's conftest sets
    floor = "jax_persistent_cache_min_compile_time_secs"
    was = getattr(jax.config, floor)
    jax.config.update(floor, 0.0)
    try:
        facts = chip_smoke.phase_cache(
            chip_smoke.TINY, SEED, PLATFORM, chip_smoke.CacheCounter(),
            compile_cache.place(),
        )
    finally:
        jax.config.update(floor, was)
    assert facts["second"]["hits"] > 0 and facts["second"]["misses"] == 0


def test_phase_mesh_on_four_virtual_devices():
    assert len(jax.devices()) >= 4  # conftest gives the CPU eight
    facts = chip_smoke.phase_served(chip_smoke.TINY, SEED, PLATFORM, shards=4)
    assert facts["shards"] == 4


# ------------------------------------------------------------ the refusals
def test_no_result_when_a_query_fell_back(monkeypatch):
    """A plan that takes the documented oracle rung is still a failed
    smoke: the rung is counted, and the count must be empty."""
    import ksql_tpu.runtime.device_executor as dx
    from ksql_tpu.compiler.jax_expr import DeviceUnsupported

    def refuse(*a, **k):
        raise DeviceUnsupported("injected: plan does not lower")

    monkeypatch.setattr(dx.DeviceExecutor, "__init__", refuse)
    with pytest.raises(chip_smoke.SmokeFailure, match="backend after CREATE"):
        chip_smoke.phase_served(chip_smoke.TINY, SEED, PLATFORM)


def test_no_result_when_the_device_dispatch_fails():
    """What a program the chip's compiler refuses looks like: the statement
    succeeded (XLA compiles at the first dispatch), the query then left
    RUNNING — the drain must notice at once, not wait out its deadline."""
    with faults.inject("device.dispatch", mode="raise"):
        with pytest.raises(chip_smoke.SmokeFailure, match="left RUNNING"):
            chip_smoke.phase_served(chip_smoke.TINY, SEED, PLATFORM)


def _run_script(args, code=None, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    cmd = (
        [sys.executable, "-c", code, *args] if code
        else [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args]
    )
    return subprocess.run(
        cmd, capture_output=True, text=True, timeout=300, cwd=ROOT, env=env
    )


def test_script_refuses_without_a_tpu():
    proc = _run_script([])
    assert proc.returncode == 2, proc.stderr[-2000:]
    last = _last_json(proc.stdout)
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert '"ok": true' not in proc.stdout


def test_script_exits_nonzero_when_a_phase_raises():
    """The whole script, one phase, a fault armed under it: a traceback and
    a non-zero exit, and no last line that says ok."""
    code = (
        "import sys, chip_smoke\n"
        "from ksql_tpu.common import faults\n"
        "with faults.inject('push.residual.kernel', mode='raise'):\n"
        "    sys.exit(chip_smoke.main(sys.argv[1:]))\n"
    )
    proc = _run_script(["--rehearse", "--phases", "taps"], code=code)
    assert proc.returncode not in (0, 2), proc.stdout[-2000:]
    assert "SmokeFailure" in proc.stderr
    assert '"ok": true' not in proc.stdout


def test_script_rehearsal_names_the_platform_it_ran_on():
    proc = _run_script(["--rehearse", "--phases", "taps"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = _last_json(proc.stdout)
    count = last["device"].pop("count")  # the suite gives the CPU several
    assert count >= 1 and last == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu"},
    }
    assert '"platform": "tpu"' not in proc.stdout


# ----------------------------------------- where the compile cache goes
_ASK = (
    "import jax\n"
    "from ksql_tpu.runtime import compile_cache\n"
    "print(compile_cache.place())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def _ask_cache(env_extra, cwd=ROOT):
    env = {k: v for k, v in os.environ.items() if k != compile_cache.ENV_VAR}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT, **env_extra)
    out = subprocess.run(
        [sys.executable, "-c", _ASK], capture_output=True, text=True,
        timeout=120, cwd=cwd, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_cache_dir_from_the_environment_is_left_to_jax(monkeypatch, tmp_path):
    """Variable set: the helper sets nothing in code (JAX reads it)."""
    calls = []
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    monkeypatch.setattr(jax.config, "update", lambda *a, **k: calls.append(a))
    assert compile_cache.place() == str(tmp_path)
    assert calls == []
    placed, in_jax = _ask_cache({compile_cache.ENV_VAR: str(tmp_path)})
    assert placed == in_jax == str(tmp_path)


def test_cache_dir_defaults_into_the_checkout():
    """Variable unset: <checkout>/.jax_cache, derived from the package's
    location — no temp name, pid or time in it — and git-ignored."""
    placed, in_jax = _ask_cache({})
    assert placed == in_jax == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cache_dir_is_the_same_from_two_processes(tmp_path):
    """The path is part of the cache key: two processes, started from
    different directories, must agree on it."""
    assert _ask_cache({}, cwd=ROOT) == _ask_cache({}, cwd=str(tmp_path))
