"""Staging/tooling scripts: shard builder and corpus builder contracts."""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_make_imagenet_shards_roundtrip(tmp_path):
    PIL = pytest.importorskip("PIL")
    from PIL import Image

    r = np.random.RandomState(0)
    for cls in ["n01", "n02", "n03"]:
        d = tmp_path / "train" / cls
        d.mkdir(parents=True)
        for i in range(2):
            Image.fromarray(
                r.randint(0, 255, (50, 70, 3), dtype=np.uint8)
            ).save(d / f"im{i}.JPEG")
    out = tmp_path / "shards"
    subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "make_imagenet_shards.py"),
         "--src", str(tmp_path / "train"), "--out", str(out),
         "--split", "train", "--store-size", "32"],
        check=True, capture_output=True,
    )
    x = np.load(out / "train_x.npy")
    y = np.load(out / "train_y.npy")
    assert x.shape == (6, 32, 32, 3) and x.dtype == np.uint8
    # sorted-directory class ids, 2 images each
    assert y.tolist() == [0, 0, 1, 1, 2, 2]


def test_make_code_corpus(tmp_path):
    src = tmp_path / "src"
    src.mkdir()
    (src / "a.py").write_text("def f(x):\n    return x + 1\n" * 200)
    out = tmp_path / "corpus"
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "make_code_corpus.py"),
         "--src", str(src), "--out", str(out), "--vocab-size", "50",
         "--max-tokens", "5000"],
        check=True, capture_output=True, text=True,
    )
    assert "corpus:" in res.stdout
    for split in ("train", "valid", "test"):
        assert (out / f"wiki.{split}.tokens").is_file()
    # the trainers' corpus loader can consume it
    sys.path.insert(0, REPO)
    from kfac_pytorch_tpu.training import data as data_lib

    splits, words = data_lib.build_corpus(str(out))
    assert set(splits) == {"train", "valid", "test"}
    assert 2 < len(words) <= 52
    assert splits["train"].dtype == np.int32


def test_pallas_interpret_lint_clean():
    """Every Pallas kernel in ops/ must stay covered by an interpret-mode
    test — otherwise CPU tier-1 silently stops checking its math
    (scripts/check_pallas_interpret.py)."""
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "check_pallas_interpret.py")],
        capture_output=True, text=True, cwd=REPO,
    )
    assert res.returncode == 0, f"\n{res.stdout}{res.stderr}"
    assert "OK" in res.stdout


def test_trace_events_lint_clean():
    """Every flight-recorder event kind emitted in the package must appear
    in docs/OBSERVABILITY.md's trace-event registry, and vice versa
    (scripts/check_trace_events.py)."""
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "check_trace_events.py")],
        capture_output=True, text=True, cwd=REPO,
    )
    assert res.returncode == 0, f"\n{res.stdout}{res.stderr}"
    assert "OK" in res.stdout


def test_collective_count_check():
    """The compiled capture step must carry ≤ bucket-count factor
    all-reduces over the plain step — per-leaf collectives sneaking back in
    means the FactorComm fusion regressed — and the owner-sharded capture
    step must pin to ≤ bucket-count reduce-scatters plus exactly one
    preconditioned-gradient all-gather, with the replicated baseline free
    of both op kinds (scripts/check_collective_count.py). The 3-D
    data×fsdp×tensor section pins the shardwise factor exchange to joint
    data×fsdp replica groups with ZERO tensor-axis additions — the
    per-shard G/A blocks precondition where their kernel shard lives
    (docs/SHARDING.md)."""
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "check_collective_count.py")],
        capture_output=True, text=True, cwd=REPO,
    )
    assert res.returncode == 0, f"\n{res.stdout}{res.stderr}"
    assert "OK" in res.stdout
    assert "3-D mesh factor exchange confined" in res.stdout
    assert "zero tensor-axis additions" in res.stdout


def test_overlap_hlo_check():
    """The overlap plane's compiled capture step must issue no MORE
    all-reduces than the serial program, and in the traced jaxpr no
    gradient/loss psum may be data-dependent on a factor-bucket psum —
    overlap is a pure reorder, never a semantic rewrite
    (scripts/check_overlap_hlo.py)."""
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "check_overlap_hlo.py")],
        capture_output=True, text=True, cwd=REPO,
    )
    assert res.returncode == 0, f"\n{res.stdout}{res.stderr}"
    assert "OK" in res.stdout


def test_solver_hlo_check():
    """The solver='rsvd' refresh program must contain zero eigendecomposition
    custom-calls at/above the truncation threshold — a dense eigh sneaking
    back in means the matmul-only guarantee regressed
    (scripts/check_solver_hlo.py)."""
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "check_solver_hlo.py")],
        capture_output=True, text=True, cwd=REPO,
    )
    assert res.returncode == 0, f"\n{res.stdout}{res.stderr}"
    assert "OK" in res.stdout


def test_service_hlo_check():
    """Under ``service_devices > 0`` the compiled training step must contain
    zero eigendecomposition custom-calls and no refresh collectives, and the
    worker refresh program must contain no gradient/factor communication —
    the curvature refresh lives off the critical path or not at all
    (scripts/check_service_hlo.py)."""
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "check_service_hlo.py")],
        capture_output=True, text=True, cwd=REPO,
    )
    assert res.returncode == 0, f"\n{res.stdout}{res.stderr}"
    assert "OK" in res.stdout


def test_plan_snapshot_check():
    """The production profile's resolved plan for the three canonical
    (model, mesh) fixtures must match the checked-in goldens — silent
    cost-model drift fails tier-1 instead of changing every user's levers
    (scripts/check_plan_snapshot.py)."""
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "check_plan_snapshot.py")],
        capture_output=True, text=True, cwd=REPO,
    )
    assert res.returncode == 0, f"\n{res.stdout}{res.stderr}"
    assert "OK" in res.stdout


def test_state_manifest_check():
    """Every K-FAC state key any lever touches must appear in the elastic
    snapshot manifest (elastic/state_io.py KFAC_STATE_KEYS), and every
    manifest row must be touched by code — a future lever can't silently
    drift its state out of checkpoints (scripts/check_state_manifest.py)."""
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "check_state_manifest.py")],
        capture_output=True, text=True, cwd=REPO,
    )
    assert res.returncode == 0, f"\n{res.stdout}{res.stderr}"
    assert "OK" in res.stdout


def test_no_bytecode_artifacts_tracked():
    """git must never track __pycache__ directories or .pyc files — stale
    bytecode shadows source edits and bloats the repo."""
    res = subprocess.run(
        ["git", "ls-files"], capture_output=True, text=True, cwd=REPO,
    )
    if res.returncode != 0:
        pytest.skip("not a git checkout")
    bad = [
        f for f in res.stdout.splitlines()
        if "__pycache__" in f or f.endswith(".pyc")
    ]
    assert not bad, f"bytecode artifacts tracked by git: {bad}"


def test_no_scratch_files_tracked():
    """scratch/ is the local workbench (.gitignore'd) — session experiments
    and one-off probes must never ship in the repo history."""
    res = subprocess.run(
        ["git", "ls-files", "scratch"], capture_output=True, text=True,
        cwd=REPO,
    )
    if res.returncode != 0:
        pytest.skip("not a git checkout")
    bad = res.stdout.splitlines()
    assert not bad, f"scratch files tracked by git: {bad}"


def test_summarize_curves_compare_fallback(tmp_path):
    """--compare falls back to a shared lower-is-better tag when the runs
    have no val/accuracy (LM logs), and counts wins with <= semantics."""
    import json
    import subprocess
    import sys

    for name, vals in (("a", [3.0, 2.0]), ("b", [3.5, 2.5])):
        d = tmp_path / name
        d.mkdir()
        with open(d / "scalars.jsonl", "w") as fh:
            for step, v in enumerate(vals):
                fh.write(json.dumps(
                    {"tag": "val/loss", "step": step, "value": v}) + "\n")
    out = subprocess.run(
        [sys.executable, "scripts/summarize_curves.py", "--compare",
         str(tmp_path / "a"), str(tmp_path / "b")],
        capture_output=True, text=True, cwd=REPO, check=True,
    ).stdout
    assert "(comparing 'val/loss')" in out
    assert "on 2/2 epochs" in out
