"""Flash attention on the chip: ops/flash_attention.py's three kernels at the
benchmark's two shapes, float32, causal: milliseconds by the host's clock
round ``block_until_ready`` for forward, dq and dk/dv apart and for the whole
forward + backward, over block sizes; the parent's kernels (one 128 x 128
tile a grid step) where ``--parent`` names its file; the kernel that ships
with JAX (``jax.experimental.pallas.ops.tpu.flash_attention``) at a few
``BlockSizes`` as the yardstick; XLA's own attention; and the results'
distance from float64 on the host (which arithmetic does Mosaic give float32
operands at the precision left unset?). Run by hand through the chip tool
(about five chip minutes); one JSON line per reading, also appended to
``chiprun_out/flash_sweep.jsonl``.

    python scripts/flash_sweep.py [--parent <parent's ops/flash_attention.py>] [--reps 20]
"""

import argparse
import importlib.util
import json
import math
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from kfac_pytorch_tpu.ops import flash_attention as fa
from kfac_pytorch_tpu.parallel.context import full_attention

SHAPES = {"gpt2_124m": (8, 1024, 12, 64), "glm47_flash_ep8": (1, 2048, 20, 256)}
BLOCKS = ((128, 128), (256, 128), (256, 256), (512, 128), (512, 256), (512, 512), (1024, 256), (1024, 512))
OUT = os.path.join(ROOT, "chiprun_out", "flash_sweep.jsonl")


def say(**row):
    line = json.dumps(row)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def timed(fn, *args, reps=20):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        res = fn(*args)
    jax.block_until_ready(res)
    return out, (time.perf_counter() - t0) / reps * 1e3


def float64_heads(q, k, v, g, heads=2):
    """Causal attention and its three gradients for batch 0's first heads, in
    float64 on the host: ``[T, heads, D]`` each."""
    q, k, v, g = (np.asarray(x[0, :, :heads], np.float64) for x in (q, k, v, g))
    t, _, d = q.shape
    scale = 1.0 / math.sqrt(d)
    mask = np.tril(np.ones((t, t), bool))
    outs = [np.zeros_like(q) for _ in range(4)]
    for h in range(heads):
        s = np.where(mask, (q[:, h] * scale) @ k[:, h].T, -np.inf)
        p = np.exp(s - s.max(axis=1, keepdims=True))
        p /= p.sum(axis=1, keepdims=True)
        o = p @ v[:, h]
        dp = g[:, h] @ v[:, h].T
        ds = p * (dp - np.sum(g[:, h] * o, axis=1, keepdims=True))
        for dst, val in zip(outs, (o, ds @ k[:, h] * scale, ds.T @ q[:, h] * scale, p.T @ g[:, h])):
            dst[:, h] = val
    return outs


def gaps(got, want):
    """Largest distance over the reference's largest entry: out, dq, dk, dv."""
    heads = want[0].shape[1]
    return [float(np.abs(np.asarray(x[0, :, :heads], np.float64) - w).max() / np.abs(w).max())
            for x, w in zip(got, want)]


def whole(attn):
    """Forward and backward of ``attn(q, k, v)`` as one program: out, dq, dk, dv."""
    def fn(q, k, v, g):
        out, pull = jax.vjp(attn, q, k, v)
        return (out, *pull(g))
    return jax.jit(fn)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", help="the parent commit's ops/flash_attention.py")
    ap.add_argument("--reps", type=int, default=20)
    a = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"flash_sweep: no TPU (found {device.platform}); a CPU time is no device number")
    say(device=device.device_kind, platform=device.platform, reps=a.reps)
    parent = None
    if a.parent:
        spec = importlib.util.spec_from_file_location("parent_flash_attention", a.parent)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    from jax.experimental.pallas.ops.tpu import flash_attention as shipped

    rng = np.random.default_rng(0)
    for config, shape in SHAPES.items():
        b, t, h, d = shape
        q, k, v, g = (jnp.asarray(rng.standard_normal(shape), jnp.float32) for _ in range(4))
        want = float64_heads(q, k, v, g)
        row = dict(config=config, shape=list(shape))

        _, ms = timed(whole(lambda q, k, v: full_attention(q, k, v, causal=True)), q, k, v, g, reps=a.reps)
        say(**row, path="xla full_attention", whole_ms=ms)

        if parent is not None:
            fwd = jax.jit(lambda q, k, v: parent._flash_forward(q, k, v, True, 128, 128, False))
            (out, lse), fwd_ms = timed(fwd, q, k, v, reps=a.reps)
            bwd = jax.jit(lambda q, k, v, o, l, g: parent._flash_backward(q, k, v, o, l, g, True, 128, 128, False))
            _, bwd_ms = timed(bwd, q, k, v, out, lse, g, reps=a.reps)
            got, ms = timed(whole(lambda q, k, v: parent.flash_attention(q, k, v)), q, k, v, g, reps=a.reps)
            say(**row, path="parent 128/128", fwd_ms=fwd_ms, dq_dkv_ms=bwd_ms, whole_ms=ms,
                grid_steps=3 * b * h * (t // 128) ** 2, gap_out_dq_dk_dv=gaps(got, want))

        chosen = fa._choose_blocks(t, d, 4)
        for block_q, block_k in BLOCKS + ((None, None),):
            tiling = fa._choose_blocks(t, d, 4, block_q, block_k)
            name = f"ours {tiling.block_q}/{tiling.block_k}" + ("" if block_q else " (chosen)")
            try:
                fwd = jax.jit(lambda q, k, v: fa._flash_forward(q, k, v, True, tiling, False))
                (out, lse), fwd_ms = timed(fwd, q, k, v, reps=a.reps)
                halves = [fa._heads_major(x) for x in (q, k, v, g)]
                delta = jnp.broadcast_to(jnp.sum(fa._heads_major(out) * halves[3], -1)[..., None], lse.shape)
                dq = jax.jit(lambda *xs: fa._flash_dq(*xs, True, tiling, False))
                _, dq_ms = timed(dq, *halves, lse, delta, reps=a.reps)
                dkv = jax.jit(lambda *xs: fa._flash_dkv(*xs, True, tiling, False))
                _, dkv_ms = timed(dkv, *halves, lse, delta, reps=a.reps)
                got, ms = timed(whole(lambda q, k, v: fa._flash(q, k, v, True, tiling, False)), q, k, v, g, reps=a.reps)
            except Exception as e:  # noqa: BLE001 — a refused tiling is a reading too
                say(**row, path=name, tiling=list(tiling), refused=str(e)[:300])
                continue
            say(**row, path=name, tiling=list(tiling), fwd_ms=fwd_ms, dq_ms=dq_ms, dkv_ms=dkv_ms, whole_ms=ms,
                kv_resident=tiling.major == t, live_share=fa._live_tiles(t, tiling, True) / t**2,
                grid_steps=2 * b * h * (t // tiling.block_q) + b * h * (t // tiling.block_k),
                gap_out_dq_dk_dv=gaps(got, want), is_chosen=tiling == chosen)

        for bq, major, bk in ((128, 128, 128), (512, 512, 512), (512, t, 512), (1024, t, 512), (512, t, 256)):
            swap = lambda x: x.transpose(0, 2, 1, 3)  # it wants [B, H, T, D]: ours pays the same two
            attn = lambda q, k, v: swap(shipped.flash_attention(
                swap(q), swap(k), swap(v), causal=True, sm_scale=1.0 / math.sqrt(d), block_sizes=sizes))
            name = f"shipped {bq}/{major}/{bk}"
            try:
                sizes = shipped.BlockSizes(
                    block_q=bq, block_k_major=major, block_k=bk, block_b=1,
                    block_q_major_dkv=bq, block_k_major_dkv=major, block_k_dkv=bk, block_q_dkv=bq,
                    block_k_major_dq=major, block_k_dq=bk, block_q_dq=bq)
                _, fwd_ms = timed(jax.jit(attn), q, k, v, reps=a.reps)
                got, ms = timed(whole(attn), q, k, v, g, reps=a.reps)
            except Exception as e:  # noqa: BLE001
                say(**row, path=name, refused=str(e)[:300])
                continue
            say(**row, path=name, fwd_ms=fwd_ms, whole_ms=ms, gap_out_dq_dk_dv=gaps(got, want))


if __name__ == "__main__":
    main()
