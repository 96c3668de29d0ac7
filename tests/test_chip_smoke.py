"""``chip_smoke.py`` off the chip: its control flow at a tiny size, its
refusal to pass without a TPU, and where the compile cache goes.

The script is run in child processes, as the driver runs it (x64 off —
the suite's conftest turns it on for the parent only).  ``--rehearse``
is the script's own switch for tiny sizes with interpret-mode kernels;
the driver never gives it.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, **env_extra):
    env = {
        k: v for k, v in os.environ.items()
        if not k.startswith("SKYLARK_") and k != "XLA_FLAGS"
    }
    # The child's cache goes where the environment says: not into the
    # checkout the suite runs from.
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.update(env_extra)
    proc = subprocess.run(
        [sys.executable, SCRIPT, *args], env=env, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=600,
    )
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return proc, lines


def test_without_a_tpu_it_fails_and_prints_no_result(tmp_path):
    proc, lines = _run([], tmp_path)
    assert proc.returncode != 0
    last = lines[-1] if lines else ""
    assert '"ok": true' not in last
    assert '"platform": "tpu"' not in last
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("chips", [1, 4])
def test_rehearsal_runs_every_phase(tmp_path, chips):
    extra = {}
    if chips > 1:
        extra["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    proc, lines = _run(["--rehearse", "--chips", str(chips), "--seed", "3"],
                       tmp_path, **extra)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = [json.loads(ln) for ln in lines]  # every line is one JSON object
    last = rows[-1]
    assert last["ok"] is True and last["rehearsal"] is True
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": chips}
    phases = [r["phase"] for r in rows[:-1]]
    want = (
        ["start", "multichip", "serve", "multichip_placement", "total"]
        if chips > 1
        else ["start", "sketch", "solve", "native", "train", "serve", "total"]
    )
    assert phases == want
    assert rows[0]["x64"] is False
    assert rows[0]["compile_cache_dir"] == str(tmp_path / "cache")
    by = {r["phase"]: r for r in rows[:-1]}
    serve = by["serve"]["checks"]
    assert serve["compile_requests_after_prime"] == [0.0, 0]
    assert serve["no_error_envelope"] is True and serve["coalesced"] is True
    if chips == 1:
        routes = by["sketch"]["routes"]
        assert routes["JLT bf16 rowwise"] == "gemm"
        assert set(routes.values()) <= {"gemm", "xla", "interpret"}
        for phase in ("sketch", "solve", "train"):
            for name, v in by[phase]["checks"].items():
                assert v is True or v[0] <= v[1], (phase, name, v)


def test_cache_dir_follows_env_else_fixed_path(tmp_path):
    code = (
        "import jax\n"
        "from libskylark_tpu.utils import compile_cache\n"
        "print(compile_cache.place('/ignored/by/env'))\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)

    def child(**extra):
        out = subprocess.run(
            [sys.executable, "-c", code], env={**env, **extra},
            cwd=str(tmp_path), capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        return out.stdout.split()

    there = str(tmp_path / "from-env")
    assert child(JAX_COMPILATION_CACHE_DIR=there) == [there, there]

    from libskylark_tpu.utils import compile_cache

    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.FIXED_DIR == fixed
    code = code.replace("'/ignored/by/env'", "")
    assert child() == [fixed, fixed]


def test_guards_are_cut_only_on_a_slow_host():
    """All eleven guards run unless too much of the 1200 s is gone
    before they start; then the guards of compiled kernels still do."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import _hw_guards

    names = [n for n, _ in _hw_guards.GUARDS]
    assert len(names) == 11 and cs.KERNEL_GUARDS <= set(names)
    assert cs.select_guards(names, 110.0) == (names, [])
    run_now, cut = cs.select_guards(names, cs.GUARDS_CUT_AFTER_S + 1)
    assert set(run_now) == cs.KERNEL_GUARDS
    assert sorted(run_now + cut) == sorted(names)

