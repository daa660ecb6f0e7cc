"""End-to-end trainer CLI runs (in-process, tiny configs, 8-dev CPU mesh).

The reference's trainers were only ever validated by running them
(SURVEY.md §4); here the augmented-ImageNet path — uint8 shards → native (or
numpy) RandomResizedCrop/CenterCrop+normalize → sharded K-FAC train step →
masked full-split eval → checkpoint — runs as a test, so pipeline/trainer
regressions surface in the suite rather than on the chip.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples")
)


@pytest.fixture()
def imagenet_shards(tmp_path):
    r = np.random.RandomState(0)
    d = tmp_path / "shards"
    d.mkdir()
    for split, n in [("train", 48), ("val", 20)]:
        np.save(d / f"{split}_x.npy",
                r.randint(0, 256, size=(n, 40, 40, 3), dtype=np.uint8))
        np.save(d / f"{split}_y.npy", r.randint(0, 1000, size=n).astype(np.int32))
    return d


@pytest.mark.slow  # ~5-7 min of 8-device XLA compile on CPU
def test_imagenet_trainer_end_to_end(imagenet_shards, tmp_path):
    import train_imagenet_resnet as t

    log_dir = tmp_path / "logs"
    run = t.main([
        "--data-dir", str(imagenet_shards),
        "--image-size", "32", "--val-resize", "36",
        "--model", "resnet18",
        "--batch-size", "1", "--val-batch-size", "1",
        "--epochs", "1", "--steps-per-epoch", "3",
        "--kfac-update-freq", "2", "--kfac-cov-update-freq", "1",
        "--eigen-dtype", "bf16",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--log-dir", str(log_dir),
    ])
    state = run.state
    assert int(state.step) == 3
    assert len(run.step_losses) == 3 and run.kfac.precond_method == "eigen"
    scalars = log_dir / "scalars.jsonl"
    assert scalars.is_file()
    tags = {json.loads(l)["tag"] for l in scalars.open()}
    assert {"train/loss", "val/loss", "val/accuracy"} <= tags
    # checkpoint written
    assert any((tmp_path / "ckpt").iterdir())


def test_imagenet_trainer_rejects_undersized_val_resize(imagenet_shards):
    import train_imagenet_resnet as t

    with pytest.raises(SystemExit):
        t.main([
            "--data-dir", str(imagenet_shards),
            "--image-size", "224", "--val-resize", "192",
        ])


@pytest.mark.slow  # ~5-7 min of 8-device XLA compile on CPU
def test_evaluate_cli_matches_trainer_val(imagenet_shards, tmp_path):
    """examples/evaluate.py on the trainer's checkpoint reproduces the
    trainer's final val metrics (same weights, same shared eval path)."""
    import json

    import evaluate as ev
    import train_imagenet_resnet as t

    log_dir = tmp_path / "logs"
    t.main([
        "--data-dir", str(imagenet_shards),
        "--image-size", "32", "--val-resize", "36",
        "--model", "resnet18",
        "--batch-size", "1", "--val-batch-size", "1",
        "--epochs", "1", "--steps-per-epoch", "2",
        "--kfac-update-freq", "2", "--kfac-cov-update-freq", "1",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--log-dir", str(log_dir),
    ])
    want = {
        json.loads(l)["tag"]: json.loads(l)["value"]
        for l in (log_dir / "scalars.jsonl").open()
    }
    loss, acc = ev.main([
        "--data-dir", str(imagenet_shards),
        "--model", "resnet18",
        "--image-size", "32", "--val-resize", "36",
        "--batch-size", "1", "--num-workers", "0",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
    ])
    assert abs(loss - want["val/loss"]) < 1e-4
    assert abs(acc - want["val/accuracy"]) < 1e-6


def test_wikitext_rnn_trainer_smoke(tmp_path):
    """The third workload end-to-end in tier-1: synthetic corpus → LSTM
    with tied decoder + diagonal-A embedding K-FAC (the reduce lens) →
    planner-checked levers → scalars. The reference's wikitext trainer
    could never run K-FAC at all (pytorch_wikitext_rnn.py:6)."""
    import json

    import train_wikitext_rnn as t

    log_dir = tmp_path / "logs"
    state = t.main([
        "--synthetic",
        "--model", "LSTM", "--emsize", "12", "--nhid", "12",
        "--nlayers", "1", "--dropout", "0.0",
        "--tied", "--kfac-embedding",
        "--batch-size", "8", "--bptt", "4",
        "--epochs", "1", "--steps-per-epoch", "3",
        "--base-lr", "0.5",
        "--kfac-update-freq", "2", "--kfac-cov-update-freq", "1",
        "--log-dir", str(log_dir),
    ])
    assert state is not None
    assert int(state.step) == 3
    # the tied embedding/decoder pair preconditions as ONE diag-A layer
    facs = state.kfac_state["factors"]
    emb = [n for n in facs if "A_diag" in facs[n]]
    assert len(emb) == 1, facs.keys()
    tags = {
        json.loads(l)["tag"]
        for l in (log_dir / "scalars.jsonl").open()
    }
    assert {"train/loss", "train/ppl", "val/loss", "val/ppl"} <= tags


def test_wikitext_rnn_rejects_invalid_lever_composition(tmp_path):
    """Lever validation goes through the planner's validity matrix: a
    staleness budget without any deferral lever must refuse with the
    matrix's reason, not train silently."""
    import train_wikitext_rnn as t

    with pytest.raises(SystemExit, match="staleness"):
        t.main([
            "--synthetic", "--epochs", "1", "--steps-per-epoch", "1",
            "--emsize", "12", "--nhid", "12", "--nlayers", "1",
            "--staleness-budget", "2",
            "--log-dir", str(tmp_path / "logs"),
        ])


def test_evaluate_cli_arg_validation(imagenet_shards):
    import evaluate as ev
    import pytest as _pytest

    with _pytest.raises(SystemExit):
        ev.main(["--data-dir", str(imagenet_shards), "--model", "resnet18"])
