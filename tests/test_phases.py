"""Phase scopes in the step programs (observability/phases.py).

The scopes are ``jax.named_scope`` names on the ops of the compiled step:
HLO metadata only. These tests lower the step programs of a tiny LM and a
tiny conv net with debug info and read the op-name paths: every program
carries the phases it should and none it should not, the factor products of
``ops/factors.py`` sit under ``kfac_capture`` wherever they are traced from,
and the lowered program is the same text with telemetry on and off.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfac_pytorch_tpu import KFAC, capture
from kfac_pytorch_tpu.models import cifar_resnet, transformer_lm
from kfac_pytorch_tpu.observability import PHASES, phase
from kfac_pytorch_tpu.observability.device_phases import UNSCOPED, phase_of
from kfac_pytorch_tpu.observability.telemetry import configure, get_telemetry
from kfac_pytorch_tpu.training.lm_step import make_lm_train_step
from kfac_pytorch_tpu.training.step import TrainState, make_sgd, make_train_step

KINDS = {
    "refresh": {"update_factors": True, "update_eigen": True},
    "factors": {"update_factors": True, "update_eigen": False},
    "plain": {"update_factors": False, "update_eigen": False},
    "twin": {"update_factors": False, "update_eigen": False},
}
# what each program's ops must and must not carry (one device: no exchange)
EXPECTED = {
    "refresh": {"model", "kfac_capture", "kfac_refresh", "kfac_apply", "optimizer"},
    "factors": {"model", "kfac_capture", "kfac_apply", "optimizer"},
    "plain": {"model", "kfac_apply", "optimizer"},
    "twin": {"model", "optimizer"},
}


def _lm():
    model = transformer_lm.get_model(50, d_model=32, n_heads=2, n_layers=2)
    toks = np.random.RandomState(0).randint(0, 50, size=(4, 17))
    batch = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])
    kfac = KFAC(layers=capture.discover_layers(model, batch[0], train=True),
                damping=0.01, precond_method="inverse")
    return model, batch, kfac, {"grad_clip": 0.25}


def _conv():
    model = cifar_resnet.get_model("resnet20")
    r = np.random.RandomState(0)
    batch = (jnp.asarray(r.randn(4, 16, 16, 3).astype(np.float32)),
             jnp.asarray(r.randint(0, 10, size=4)))
    return model, batch, KFAC(damping=0.003), {}


def _lowered(net, kind, debug_info=True, **step_kw):
    model, batch, kfac, kw = net()
    kfac = None if kind == "twin" else kfac
    variables = model.init(jax.random.PRNGKey(0), batch[0], train=True)
    tx = make_sgd(momentum=0.9, weight_decay=5e-4)
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=variables["params"],
        batch_stats=variables.get("batch_stats", {}),
        opt_state=tx.init(variables["params"]),
        kfac_state=kfac.init(variables["params"]) if kfac else None,
    )
    step = make_train_step(model, tx, kfac, train_kwargs={"train": True}, **kw, **step_kw)
    accum = step_kw.get("accum_steps", 1)
    if accum > 1:  # [accum_steps, microbatch, ...]
        batch = jax.tree_util.tree_map(lambda a: a.reshape(accum, -1, *a.shape[1:]), batch)
    return step.lower(state, batch, jnp.float32(0.1), jnp.float32(0.01),
                      **KINDS[kind]).as_text(debug_info=debug_info)


def _op_names(text):
    """The op-name paths of a lowered module's named locations."""
    return re.findall(r'loc\("(jit\(train_step\)[^"]*)"', text)


@pytest.mark.parametrize("kind", list(KINDS))
@pytest.mark.parametrize("net", [_lm, _conv], ids=["lm", "conv"])
def test_step_program_carries_its_phases_and_no_other(net, kind):
    names = _op_names(_lowered(net, kind))
    found = {phase_of(n) for n in names}
    expected = set(EXPECTED[kind]) | ({"grad_clip"} if net is _lm else set())
    assert found - {UNSCOPED} == expected
    assert names  # the step keeps its jit name: the benchmark finds the program's runs by it
    # what carries no phase is bookkeeping (step counters): a handful of ops
    unscoped = [n for n in names if phase_of(n) == UNSCOPED]
    assert len(unscoped) < 0.02 * len(names), sorted(set(unscoped))


def _innermost_file(table, ref):
    """The source file of the innermost frame of MLIR location ``ref``."""
    text = table[ref]
    m = re.match(r'loc\(callsite\((#loc\d+) at ', text) or re.match(r'loc\("[^"]*"\((#loc\d+)\)\)', text)
    if m:
        return _innermost_file(table, m.group(1))
    m = re.match(r'loc\("([^"]+)":\d+', text)
    return m.group(1) if m else None


@pytest.mark.parametrize("net", [_lm, _conv], ids=["lm", "conv"])
def test_factor_products_sit_under_capture(net):
    text = _lowered(net, "factors")
    table = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))
    checked = 0
    for op, ref in re.findall(r"stablehlo\.(dot_general|convolution)\b.* loc\((#loc\d+)\)", text):
        named = re.match(r'loc\("([^"]*)"', table[ref])
        if named and (_innermost_file(table, ref) or "").endswith("ops/factors.py"):
            # the A products are traced inside the model's forward pass: the
            # innermost phase wins
            assert phase_of(named.group(1)) == "kfac_capture", named.group(1)
            checked += 1
    assert checked >= 4  # an A and a G product for two layers at the least


def test_a_products_inside_the_forward_pass_read_as_capture_not_model():
    names = _op_names(_lowered(_lm, "factors"))
    sown = [n for n in names if "/model/" in n and "kfac_capture" in n]
    assert sown and all(phase_of(n) == "kfac_capture" for n in sown)
    backward = [n for n in names if "transpose(jvp(" in n]
    assert backward and all(phase_of(n) == "model" for n in backward)


def test_microbatch_scan_counts_as_model():
    names = _op_names(_lowered(_conv, "factors", accum_steps=2, stats_all_microbatches=True))
    assert {phase_of(n) for n in names} - {UNSCOPED} == EXPECTED["factors"]
    in_scan = [n for n in names if "/while/" in n]
    assert in_scan and {phase_of(n) for n in in_scan} == {"model"}


def test_lm_step_carries_the_same_phases():
    from kfac_pytorch_tpu.models import wikitext_rnn

    model = wikitext_rnn.get_model("LSTM", ntoken=50, ninp=16, nhid=16, nlayers=1, dropout=0.1)
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 50, size=(4, 8)))
    kfac = KFAC(layers=capture.discover_layers(model, toks, train=True), damping=0.01)
    params = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)}, toks, train=True
    )["params"]
    tx = make_sgd(momentum=0.9)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params, batch_stats={},
                       opt_state=tx.init(params), kfac_state=kfac.init(params))
    from kfac_pytorch_tpu.training.lm_step import init_carry

    step = make_lm_train_step(model, tx, kfac, grad_clip=0.25)
    text = step.lower(state, (toks, toks), init_carry(model, params, toks), jax.random.PRNGKey(2),
                      jnp.float32(0.1), jnp.float32(0.01), update_factors=True,
                      update_eigen=True).as_text(debug_info=True)
    found = {phase_of(n) for n in _op_names(text)} - {UNSCOPED}
    assert found == EXPECTED["refresh"] | {"grad_clip"}


def test_lowered_text_is_the_same_with_telemetry_on_and_off():
    # the scopes are always there, and a span emits no op: with debug info
    # (op names included) the program is the same text either way
    tel = get_telemetry()
    was = tel.enabled
    try:
        configure(enabled=False)
        off, off_debug = _lowered(_lm, "refresh", debug_info=False), _lowered(_lm, "refresh")
        configure(enabled=True)
        on, on_debug = _lowered(_lm, "refresh", debug_info=False), _lowered(_lm, "refresh")
        spans = dict(tel.hists)
    finally:
        configure(enabled=was)
        tel.reset()
    assert on == off and _op_names(on_debug) == _op_names(off_debug)
    # one call site marks one phase on both clocks: the trace-time spans fired
    assert {"trace/kfac/factor_update", "trace/kfac/eigh", "trace/kfac/precondition"} <= spans.keys()


def test_phase_names_are_single_components_and_checked():
    assert all("/" not in p and re.fullmatch(r"\w+", p) for p in PHASES)
    with pytest.raises(ValueError):
        with phase("capture"):
            pass

    @phase("kfac_capture")
    def product(x):
        return x @ x.T

    text = jax.jit(product).lower(jnp.ones((2, 3))).as_text(debug_info=True)
    assert "jit(product)/kfac_capture/dot_general" in text


@pytest.mark.parametrize("tf_op,expected", [
    ("jit(train_step)/model/jvp(TransformerLM)/block_0/qkv/qkv._sow_a/kfac_capture/dot_general:", "kfac_capture"),
    ("jit(train_step)/model/transpose(jvp(TransformerLM))/block_0/out/dot_general:", "model"),
    ("jit(train_step)/model/while/body/model/transpose(jvp(kfac_capture))/mul", "kfac_capture"),
    ("jit(train_step)/kfac_apply/kij,kjl->kil/dot_general:", "kfac_apply"),
    ("jit(train_step)/kfac_refresh/cholesky:", "kfac_refresh"),
    ("jit(train_step)/add:", UNSCOPED),
    ("jit(train_step)/jvp(model_parallel)/mul", UNSCOPED),
    ("", UNSCOPED),
    (None, UNSCOPED),
])
def test_innermost_phase_component_wins(tf_op, expected):
    assert phase_of(tf_op) == expected


def test_docs_table_lists_the_phases():
    import os

    doc = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "docs", "OBSERVABILITY.md")
    with open(doc) as f:
        table = f.read().split("<!-- phases:start -->")[1].split("<!-- phases:end -->")[0]
    assert tuple(re.findall(r"^\| `(\w+)` \|", table, re.M)) == PHASES
