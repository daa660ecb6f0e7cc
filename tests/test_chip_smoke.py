"""CPU rehearsals of chip_smoke.py (on-chip-measurement guide, section 2).

``chip_smoke.py`` itself has no way to pass without a chip, so these call
its functions at a tiny size — ResNet-18, 32x32, batch 2 — each in a child
process that asks for the CPU explicitly (``KFAC_FORCE_PLATFORM=cpu:N``):
one device for the one-chip phase, four virtual devices for the four-chip
phase. A rehearsal finds wrong paths, flags, meshes and control flow; it
gives no device number.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = 'model="resnet18", image_size=32, batch_size=2'


def _python(code, devices, tmp_path):
    env = dict(os.environ)
    env.update(KFAC_FORCE_PLATFORM=f"cpu:{devices}", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, env=env, cwd=REPO,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_one_chip_phase_rehearsal(tmp_path):
    report = _python(
        "import json, chip_smoke as c\n"
        f"print(json.dumps(c.one_chip({TINY})))",
        1, tmp_path,
    )
    # here the "chip" is the CPU too, so the reference agrees to f32 rounding
    assert abs(report["step_losses"][0] - report["cpu_first_loss"]) < 1e-4
    assert sorted(set(report["step_programs"])) == ["factors", "plain", "refresh"]
    assert report["step_programs_compiled"] == 3
    assert len(report["step_losses"]) == 6
    assert report["factor_kernel"] == "dense"
    assert report["compile_seconds"] > 0 and report["backend_compiles"] >= 3


def test_four_chip_phase_rehearsal(tmp_path):
    report = _python(
        "import json, chip_smoke as c\n"
        f"print(json.dumps(c.four_chips({TINY})))",
        4, tmp_path,
    )
    assert report["owners"] == [0, 1, 2, 3]
    assert "--distribute-precondition" in report["argv"]
    got, want = report["step_losses"], report["one_device_losses"]
    assert len(got) == len(want) == 6
    # f32 on the CPU: far inside the tolerance the script states for the MXU
    assert max(abs(a - b) / abs(b) for a, b in zip(got, want)) < 5e-3


def test_exits_nonzero_without_a_chip():
    """No TPU: non-zero exit before anything compiles, no result line."""
    env = dict(os.environ)
    env.pop("KFAC_FORCE_PLATFORM", None)
    env["JAX_PLATFORMS"] = "cpu"
    for argv in ([], ["--chips", "4"]):
        res = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
            capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
        )
        assert res.returncode != 0
        assert "'platform': 'cpu'" in res.stderr, res.stderr[-2000:]
        assert not res.stdout.strip(), res.stdout


def test_forced_cpu_is_no_way_past_the_device_check():
    env = dict(os.environ, KFAC_FORCE_PLATFORM="cpu:1")
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert res.returncode != 0 and not res.stdout.strip()


def test_no_except_around_a_phase():
    src = open(os.path.join(REPO, "chip_smoke.py")).read()
    assert "except" not in src and "try:" not in src


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_is_placed_from_outside(from_env, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: no directory is set in code and the
    entries land there. Unset: the fixed <checkout>/.jax_cache."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from kfac_pytorch_tpu.compile_cache import enable_persistent_cache\n"
        "import kfac_pytorch_tpu.compile_cache as cc\n"
        "calls = []\n"
        "orig = jax.config.update\n"
        "jax.config.update = lambda k, v: (calls.append(k), orig(k, v))[1]\n"
        "path = enable_persistent_cache()\n"
        "jax.config.update = orig\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones((8, 8))).block_until_ready()\n"
        "import json; print(json.dumps({'path': path, 'calls': calls,\n"
        "    'configured': jax.config.jax_compilation_cache_dir}))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(REPO, ".jax_cache")
    if from_env:
        want = str(tmp_path / "x")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env=env, cwd=str(tmp_path),
    )
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["path"] == want and out["configured"] == want
    assert ("jax_compilation_cache_dir" in out["calls"]) == (not from_env)
    assert os.listdir(want), "no cache entry was written"


def test_repo_knows_no_cache_knob_of_its_own():
    res = subprocess.run(
        ["git", "grep", "-l", "KFAC_COMPILE_" + "CACHE", "--", ".", ":!ISSUE.md"],
        capture_output=True, text=True, cwd=REPO,
    )
    if res.returncode not in (0, 1):
        pytest.skip("not a git checkout")
    assert not res.stdout.strip(), res.stdout


def test_failed_multiprocess_start_raises(monkeypatch):
    """A run that asked for several processes must not carry on as one."""
    import jax

    from kfac_pytorch_tpu.parallel import launch

    def refuse(**kwargs):
        raise RuntimeError("coordinator unreachable")

    monkeypatch.setattr(jax.distributed, "initialize", refuse)
    monkeypatch.setattr(launch, "_initialized", False)
    with pytest.raises(RuntimeError, match="coordinator unreachable"):
        launch.initialize(
            coordinator_address="127.0.0.1:1", num_processes=2, process_id=0
        )
    assert launch._initialized is False
