"""The main path's kernels, compiled for a DESCRIBED v5e at ResNet-50 widths.

The TPU compiler is installed next to the CPU backend and compiles for a
chip that is described and not attached (on-chip-measurement guide, section
2). A compile that passes here is not a chip run: nothing executes, so this
says nothing about results or times — only that the chip's compiler accepts
the default (dense XLA) path at real shapes, which interpret-mode tests
cannot show. The Pallas kernels it refuses are listed in docs/PERF.md,
"Refused by the v5e compiler".

This is the ONLY test file that describes a chip: one process at a time may
load the TPU library, so the topology is described inside a module-scoped
fixture (never at import, in a skipif or in parametrize arguments), every
compile runs in this process, and whole step programs — minutes each — stay
in scripts/compile_for_chip.py.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from kfac_pytorch_tpu.ops import eigh, factors
from kfac_pytorch_tpu.ops import precondition as precond_ops
from kfac_pytorch_tpu.ops.flash_attention import flash_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable can be written to the persistent cache but
    # not read back without a chip: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes, **static):
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes,
    )
    compiled = jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled.as_text()


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


@pytest.mark.parametrize("g,a", [(512, 4608), (2048, 512)])
def test_dense_precondition_compiles(one_chip, g, a):
    """ops/precondition.py::precondition_all for ResNet-50's widest conv
    (layer4 3x3: 512 x 4608) and widest 1x1 (2048 x 512), one singleton and
    one stacked pair each — the every-step path under the default kernels."""
    entry = {"QA": _f32(a, a), "QG": _f32(g, g), "dA": _f32(a), "dG": _f32(g)}
    grads = {n: _f32(g, a) for n in ("l0", "l1", "solo")}
    # same-shape layers are batched; a third layer of another shape is not
    grads["solo"] = _f32(g, a + 1)
    eigen = {
        "l0": entry, "l1": entry,
        "solo": {**entry, "QA": _f32(a + 1, a + 1), "dA": _f32(a + 1)},
    }
    hlo = _compile(
        lambda gm, e, d: precond_ops.precondition_all(gm, e, d),
        one_chip, grads, eigen, _f32(),
    )
    assert "tpu_custom_call" not in hlo  # dense XLA, no Mosaic kernel


def test_dense_conv_factor_compiles(one_chip):
    """ops/factors.py::compute_a_conv (im2col) for the 3x3 conv on 56x56x64
    at batch 32 — the capture the Pallas kernel was meant to replace."""
    hlo = _compile(
        factors.compute_a_conv, one_chip, _f32(32, 56, 56, 64),
        kernel_size=(3, 3), strides=(1, 1), padding="SAME", has_bias=False,
    )
    assert "tpu_custom_call" not in hlo


@pytest.mark.parametrize("rows,side,has_bias", [
    (8192, 3072, False),  # GPT-2's widest factor: G of `fc`
    (8192, 3072, True),  # A of `mlp proj`, 3073 with its bias column
    (8192, 768, True),  # the narrowest side that takes the blocked form
    (6272, 4608, False),  # ResNet-50's widest: 128 x 7 x 7 rows in tiles of 128
    (8192, 1152, False),  # a last block of 128 columns that overhangs
    (392, 2304, True),  # few rows (8 x 7 x 7): one tile of all of them
])
def test_blocked_factor_product_compiles_and_copies_no_slice(one_chip, monkeypatch, rows, side, has_bias):
    """ops/factors.py::_gram where the factor is formed from the column-block
    pairs on and above the diagonal: two Mosaic calls (products, mirror) the
    chip's compiler accepts, no product outside them, and nothing that would
    eat the gain: no column slice, no scaled or transposed copy of the operand
    written out. The kernels read their blocks of the operand in place, so the
    only values with `rows` rows are the operand and a prefetch of the whole of it."""
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    # a described chip leaves jax.default_backend() at "cpu": compile, not interpret
    monkeypatch.setattr(factors, "gram_blocks", functools.partial(factors.gram_blocks, interpret=False))
    assert factors._gram_tiles(rows, side) is not None
    hlo = _compile(factors.compute_a_dense, one_chip, _f32(rows, side), has_bias=has_bias)
    entry = hlo[hlo.index("\nENTRY"):]
    assert entry.count('custom_call_target="tpu_custom_call"') == 2
    assert "convolution" not in hlo and " dot(" not in hlo
    tall = re.findall(rf"^\s*(?:ROOT )?%\S+ = \(?f32\[{rows},(\d+)\]\S* ([\w-]+)\(", entry, re.M)
    # (with a bias column the compiler prefetches the whole operand, asynchronously,
    # into the nearer memory space for the column sums: not a slice, not a transpose)
    assert {cols for cols, _ in tall} == {str(side)}, tall
    assert {op for _, op in tall} <= {"parameter", "copy-start", "copy-done"}, tall


def test_eigh_smallest_bucket_compiles(one_chip):
    """ops/eigh.py::batched_eigh at the 128 bucket. Larger buckets take
    minutes each to compile (docs/PERF.md): scripts/compile_for_chip.py."""
    _compile(eigh.batched_eigh, one_chip, _f32(1, 128, 128))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_fwd_bwd_compiles(one_chip, dtype):
    """ops/flash_attention.py at the transformer example's head sizes
    (4 heads of 64, seq 128): kept by best_attention_fn because the chip's
    compiler accepts forward and backward."""
    x = jax.ShapeDtypeStruct((8, 128, 4, 64), dtype)

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    hlo = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), one_chip, x, x, x)
    assert hlo.count("tpu_custom_call") >= 3  # fwd + two bwd kernels


@pytest.mark.parametrize("shape,resident", [
    ((8, 1024, 12, 64), True), ((1, 2048, 20, 256), True), ((1, 8192, 4, 128), False),
], ids=["gpt2_124m", "glm47_flash_ep8", "beyond_residency"])
def test_flash_attention_chosen_tiling_compiles_at_the_cells_shapes(one_chip, shape, resident):
    """The benchmark's two attention shapes, float32, at the blocks
    `_choose_blocks` gives them (nothing passed, as the cells' builders pass
    nothing): a head's K and V resident, key sub-blocks swept inside the grid
    step, a VMEM limit over the compiler's default for GLM's heads of 256; and
    a length whose K and V exceed the budget, which takes k-major blocks with
    a clamped index map (no cell runs it). That the chip's compiler takes the
    chosen tiling is a fact of this file, not a chip call's surprise."""
    from kfac_pytorch_tpu.ops.flash_attention import _choose_blocks

    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    assert (_choose_blocks(shape[1], shape[3], 4).major == shape[1]) == resident

    def loss(q, k, v):
        return flash_attention(q, k, v).sum()

    hlo = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), one_chip, x, x, x)
    entry = hlo[hlo.index("\nENTRY"):]
    assert entry.count('custom_call_target="tpu_custom_call"') == 3  # fwd, dq, dk/dv
    assert " dot(" not in hlo and "convolution" not in hlo  # no product outside them


def test_head_and_closed_form_loss_read_the_logits_once(one_chip):
    """GPT-2 small's head, training/step.py's cross-entropy and its gradient at
    the cells' shapes (8 x 1024 rows, 50,257 classes): of the fusions that
    touch the `[8, 1024, 50257]` logits three are products (the head's, which
    also gives the row maximum, and its two transposes, with `softmax -
    onehot` formed in their operands) and ONE is a reduction (sum of
    exponentials, first maximal index and the label's logit together).
    Nothing copies the array (the head writes it classes-second-minor: a
    flattened `[rows, classes]` view would), nothing else as large is written,
    and nothing gathers from it: on the chip a gather of the labels' logits
    moved the whole step program's activations out of fast memory (PERF.md,
    section 6, PR 31)."""
    from kfac_pytorch_tpu.training.step import cross_entropy_and_accuracy

    b, t, d, v = 8, 1024, 768, 50257

    def step(h, w, labels):
        def loss_fn(h, w):
            return cross_entropy_and_accuracy(jnp.einsum("btd,vd->btv", h, w), labels)

        return jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)(h, w)

    args = [
        jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
        for s, dt in (((b, t, d), jnp.float32), ((v, d), jnp.float32), ((b, t), jnp.int32))
    ]
    compiled = jax.jit(step).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.8e9  # the logits, 1.65e9, and little else
    hlo = compiled.as_text()
    bodies = dict(re.findall(r"\n%?(fused_computation[\w.]*) [^\n]*\{\n(.*?)\n\}", hlo, re.S))
    entry = hlo[hlo.index("\nENTRY"):]
    logits = f"f32[{b},{t},{v}]"
    kinds = []
    producers = set()
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\((.*?)\)(?:, |$)", line)
        if not m:
            continue
        name, result, op, operands = m.groups()
        if logits in result:
            producers.add(name)
        if not (logits in result or producers & set(re.findall(r"%([\w.-]+)", operands))):
            continue
        if op == "get-tuple-element":
            continue
        assert op == "fusion", line  # no copy, transpose or reshape of the array on its own
        body = bodies[re.search(r"calls=%?([\w.]+)", line).group(1)]
        kinds.append(
            "product" if " convolution(" in body or " dot(" in body
            else "gather" if " gather(" in body
            else "reduction" if " reduce(" in body
            else "other"
        )
    assert sorted(kinds) == ["product", "product", "product", "reduction"], kinds


def test_bank_preconditioned_from_its_rows_reads_the_tables_in_place(one_chip, monkeypatch):
    """ops/precondition.py::precondition_bank_rows at GLM-4.7-Flash's gate
    bank (8 experts, 2048 -> 1536, 8192 routed rows): three Mosaic calls (the
    rows times iA, the cotangents times iG, one grouped outer product) at
    float32, the kernels' nearest to the apply's `high`; the inverses read
    where they lie in their tables (no slice of a table is copied out) and no
    temporary above the three products' own outputs."""
    from jax import lax

    from kfac_pytorch_tpu.ops import grouped

    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # compile, not interpret
    rows, a, m, e = 8192, 2048, 1536, 8
    assert grouped._use_kernels(rows)
    layout = {"iA": (a, 40, e), "iG": (m, 24, e)}

    def fn(x, dy, sizes, table_a, table_g):
        return precond_ops.precondition_bank_rows(
            x, dy, sizes, {str(a): table_a, str(m): table_g}, layout, lax.Precision.HIGH)

    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((rows, a), jnp.float32), ((rows, m), jnp.float32), ((e,), jnp.int32),
        ((80, a, a), jnp.float32), ((64, m, m), jnp.float32))]
    compiled = jax.jit(fn).lower(*args).compile()
    hlo = compiled.as_text()
    entry = hlo[hlo.index("\nENTRY"):]
    assert entry.count('custom_call_target="tpu_custom_call"') == 3
    assert f"f32[{e},{a},{a}]" not in hlo and f"f32[{e},{m},{m}]" not in hlo
    outputs = 4 * rows * (a + m) + 4 * e * a * m
    assert compiled.memory_analysis().temp_size_in_bytes < outputs + 8 * 2**20


def _computations(hlo):
    """``{name: body}`` of every computation in a compiled module's text."""
    return dict(re.findall(r"\n(?:ENTRY )?%?([\w.-]+) [^\n]*\{\n(.*?)\n\}", hlo, re.S))


def _reached(comps, roots):
    """The computations that ``roots`` call, directly or through others."""
    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        todo += re.findall(r"(?:calls|body|condition|to_apply)=%?([\w.-]+)", comps[name])
        for group in re.findall(r"branch_computations=\{([^}]*)\}", comps[name]):
            todo += re.findall(r"%?([\w.-]+)", group)
    return seen


@pytest.mark.parametrize("k,n", [(4, 1024), (4, 769)])
def test_the_refresh_sweep_copies_no_stack_inside_its_loop(one_chip, k, n):
    """ops/precondition.py::_spd_inverse_stack, the refresh's in-place sweep:
    nothing that its loop runs copies the ``[k, n, n]`` stack. A pivot whose
    Cholesky symmetrised its input read the stack in a second layout, and the
    compiler then copied the whole stack twice a pivot, which ate the sweep's
    gain on the chip (PERF.md, section 6, PR 38)."""
    hlo = _compile(precond_ops._spd_inverse_stack, one_chip, _f32(k, n, n))
    comps = _computations(hlo)
    loops = _reached(comps, re.findall(r" while\([^\n]*body=%?([\w.-]+)", hlo))
    assert loops
    stack = f"f32[{k},{n},{n}]"
    copies = [line for name in loops for line in comps[name].splitlines()
              if re.search(r" copy(-start)?\(", line) and stack in line.split(" copy")[0]]
    assert not copies, copies
