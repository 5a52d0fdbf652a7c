"""The chip smoke's own logic, on the CPU.

``chip_smoke.py`` is judged on a machine with a TPU; what can be checked
here is everything around the device: the platform gate, the exit codes,
the shape of its last line, that a failing stage fails the run, and where
the compile cache goes.  Each test runs it the way an operator does — a
fresh process whose platform comes from the environment (the script itself
never sets ``JAX_PLATFORMS`` or ``XLA_FLAGS``).
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(argv, *, devices, cache_dir, timeout=600):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS",
                        "JAX_COMPILATION_CACHE_DIR",
                        "MPI4JAX_TPU_TEST_PLATFORM")}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    return subprocess.run([sys.executable, *argv], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _last_json(stdout):
    """The run's last stdout line as JSON, or None if it is not JSON."""
    lines = stdout.strip().splitlines()
    try:
        return json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        return None


def test_refuses_to_run_without_a_tpu(tmp_path):
    res = _run([SMOKE], devices=8, cache_dir=tmp_path)
    assert res.returncode != 0
    # names what jax found, first thing
    first = res.stdout.splitlines()[0]
    assert "platform=cpu" in first and "devices=8" in first, res.stdout
    assert "not 'tpu'" in res.stdout
    assert _last_json(res.stdout) is None, res.stdout
    assert not list(tmp_path.iterdir()), "compiled before the gate"


def test_rehearsal_passes_on_the_eight_device_mesh(tmp_path):
    res = _run([SMOKE, "--rehearse"], devices=8, cache_dir=tmp_path)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    assert _last_json(res.stdout) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 8},
    }
    for stage in "ABC":
        assert f"stage {stage} ok on 8x cpu" in res.stdout, res.stdout
    # multi-device evidence: collectives in the compiled HLO, the ring
    # above the crossover, the wide-halo kernel on the (2, 4) grid, and
    # tensor-parallel serving over all eight
    assert "all-reduce, collective-permute, all-to-all found" in res.stdout
    assert '"large_allreduce_algo": "ring"' in res.stdout
    assert '"kernel": "model_step2_wide"' in res.stdout
    assert '"tensor_parallel": 8' in res.stdout
    # the environment's cache directory is the one that was used
    assert str(tmp_path) in res.stdout
    assert list(tmp_path.iterdir()), "nothing was cached in the env's dir"


def test_one_device_rehearsal_drives_both_domains_through_the_host_loop(
        tmp_path):
    """On one device stage B runs the flagship four times: either domain
    as one pinned program and through ``run_multisteps``, both on the
    wide-halo kernel (``auto`` on a periodic chip too, since PR 38)."""
    res = _run([SMOKE, "--rehearse"], devices=1, cache_dir=tmp_path)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    (line,) = [ln for ln in res.stdout.splitlines() if "stage B ok" in ln]
    info = json.loads(line.partition(" | ")[2])
    assert list(info) == ["periodic", "walled", "walled_host_loop",
                          "periodic_host_loop"]
    for name, run in info.items():
        periodic = name.startswith("periodic")
        assert run["periodic_x"] is periodic and run["steps"] == 5, name
        assert run["kernel"] == "model_step2_wide", name


def test_a_failing_stage_fails_the_run(tmp_path):
    code = (
        "import sys, chip_smoke\n"
        "def boom(ctx):\n"
        "    chip_smoke.check(ctx['n'] == 0, 'seeded failure')\n"
        "stages = (('A', lambda ctx: {'fine': True}), ('B', boom))\n"
        "sys.exit(chip_smoke.main(['--rehearse'], stages=stages))\n"
    )
    res = _run(["-c", code], devices=2, cache_dir=tmp_path)
    assert res.returncode not in (0, 2), res.stdout + res.stderr
    assert "stage A ok" in res.stdout and "stage B FAILED" in res.stdout
    assert "seeded failure" in res.stderr
    assert _last_json(res.stdout) is None, res.stdout


_CACHE_PROBE = (
    "import jax\n"
    "from mpi4jax_tpu.utils.compile_cache import ensure_compile_cache\n"
    "print(ensure_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def test_cache_helper_leaves_the_environments_directory_alone(tmp_path):
    res = _run(["-c", _CACHE_PROBE], devices=1, cache_dir=tmp_path)
    assert res.returncode == 0, res.stderr
    returned, configured = res.stdout.split()
    # jax read the variable itself; the helper returned it untouched
    assert returned == configured == str(tmp_path)


def test_cache_helper_defaults_to_one_fixed_ignored_path():
    res = _run(["-c", _CACHE_PROBE], devices=1, cache_dir=None)
    assert res.returncode == 0, res.stderr
    returned, configured = res.stdout.split()
    fixed = os.path.join(REPO, ".jax_compile_cache")
    assert returned == configured == fixed
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()
