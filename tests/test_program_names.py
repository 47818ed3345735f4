"""The program names its own parts: the executable's label, forward /
backward (``value_and_grad``), the ``optimizer`` and ``attention``
scopes and the gluon blocks reach the compiled program's ``op_name``s,
``observability.perf.op_names`` hands them out lazily, and set-up leaves
spans -- all at trace time, none of it on the eager path or with
tracing off. XLA-CPU throughout."""
import re

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.contrib import nn as contrib_nn
from mxnet_tpu.observability import perf, trace


class _Net(gluon.HybridBlock):
    def __init__(self, impl):
        super().__init__()
        with self.name_scope():
            self.embed = nn.Dense(16, flatten=False)
            self.attn = contrib_nn.MultiHeadAttention(16, 2, causal=True,
                                                      impl=impl)
            self.norm = nn.LayerNorm()
            self.out = nn.Dense(4, flatten=False)

    def hybrid_forward(self, F, x):
        h = self.embed(x)
        return self.out(self.norm(h + self.attn(h)))


def _blocks(net):
    out, stack = [], [net]
    while stack:
        blk = stack.pop()
        out.append(blk.name)
        stack.extend(blk._children.values())
    return out


def _train_one_step(impl="dense"):
    perf.clear()
    net = _Net(impl)
    net.initialize()
    net(mx.nd.zeros((2, 8, 6))).wait_to_read()      # deferred shapes
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.L2Loss(), "adam", {"learning_rate": 1e-3},
        mesh=parallel.create_mesh({"dp": 1}, jax.devices()[:1]))
    rng = np.random.default_rng(0)
    loss = trainer.step(rng.random((4, 8, 6), np.float32),
                        rng.random((4, 8, 4), np.float32))
    assert np.isfinite(float(loss))
    return net, trainer


def _step_key():
    keys = [k for k, e in perf.ledger().items()
            if e["label"] == "sharded_step"]
    assert len(keys) == 1, perf.ledger().keys()
    return keys[0]


@pytest.mark.parametrize("impl", ["dense", "auto"])
def test_map_covers_the_module_and_names_phases_blocks_and_attention(impl):
    net, trainer = _train_one_step(impl)
    names = perf.op_names(_step_key())
    compiled = next(iter(trainer._step._entries.values()))
    text = compiled.as_text()
    assert text.startswith("HloModule jit_sharded_step")
    # an entry for every instruction the backend names
    instructions = set(re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", text,
                                  re.M))
    assert instructions and set(names) == instructions
    for entry in names.values():
        assert set(entry) == {"op_name", "kernel", "called", "owner", "via"}
    ops = [n["op_name"] for n in names.values() if n["op_name"]]
    forward = [o for o in ops if "jvp(" in o and "transpose(" not in o]
    backward = [o for o in ops if "transpose(jvp(" in o]
    optimizer = [o for o in ops if "/optimizer/" in o]
    assert forward and backward and optimizer
    assert all(o.startswith("jit(sharded_step)/") for o in optimizer)
    scopes = {s for o in forward + backward for s in o.split("/")}
    for block in _blocks(net):          # the root is the one jax wrapped
        assert block in scopes or f"jvp({block})" in scopes, block
    assert any("l2loss" in s for s in scopes)       # the loss block too
    # attention's core under one name in both directions, the
    # projections outside it
    inside = [o for o in ops if "/attention/" in o]
    assert any("transpose(" in o for o in inside)
    assert any("transpose(" not in o for o in inside)
    assert not [o for o in inside if "_qkv/" in o or "_out/" in o]
    # fusions say what they hold
    assert any(n["called"] for n in names.values())


def test_the_map_is_lazy_cached_and_follows_the_executable():
    _, trainer = _train_one_step()
    key = _step_key()
    assert perf.op_names("no_such@key") is None
    first = perf.op_names(key)
    assert perf.op_names(key) is first          # parsed once
    # the ledger holds the executable weakly: when its owner lets go,
    # and nothing was cached, there is nothing to name
    perf._OP_NAMES.clear()
    trainer._step = None
    import gc

    gc.collect()
    assert perf.op_names(key) is None


def test_parse_op_names_on_hlo_text_with_a_fusion_a_kernel_and_a_clone():
    text = """HloModule jit_sharded_step, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %a.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(s)/jvp(n0)/n0_d0/add" stack_frame_id=3}
  ROOT %m.2 = f32[8]{0} multiply(%a.1, %p), metadata={op_name="jit(s)/optimizer/mul"}
}

%body.3 (t: (f32[8])) -> (f32[8]) {
  %t = (f32[8]{0}) parameter(0)
  ROOT %tuple.9 = (f32[8]{0}) tuple(%t), metadata={op_name="jit(s)/transpose(jvp(n0))/n0_a/attention/while/body/add"}
}

ENTRY %main.4 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="params['w']"}
  %fusion.5 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(s)/optimizer/mul"}
  %fusion.5.remat = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(s)/jvp(n0)/n0_d0/add"}
  %copy.6 = f32[8]{0} copy(%fusion.5)
  %flash_attention_fwd.7 = f32[8]{0} custom-call(%copy.6), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/jvp(n0)/n0_a/attention/flash_attention_fwd/pallas_call" stack_frame_id=1}, backend_config={"custom_call_config":{"body":"op_name=\\"decoy\\""}}
  ROOT %while.8 = f32[8]{0} while(%flash_attention_fwd.7), condition=%body.3, body=%body.3
}
"""
    names = perf.parse_op_names(text)
    assert set(names) == {"p", "a.1", "m.2", "t", "tuple.9", "x", "fusion.5",
                          "fusion.5.remat", "copy.6",
                          "flash_attention_fwd.7", "while.8"}
    assert names["fusion.5"] == {
        "op_name": "jit(s)/optimizer/mul", "kernel": "",
        "called": ["jit(s)/jvp(n0)/n0_d0/add", "jit(s)/optimizer/mul"],
        "owner": "", "via": ""}
    assert names["fusion.5.remat"]["op_name"] == "jit(s)/jvp(n0)/n0_d0/add"
    assert names["copy.6"] == {
        "op_name": "", "kernel": "", "called": [],
        "owner": "jit(s)/jvp(n0)/n0_a/attention/flash_attention_fwd/"
                 "pallas_call", "via": "user"}
    assert names["x"]["op_name"] == "params['w']"
    assert names["flash_attention_fwd.7"]["kernel"] == "flash_attention_fwd"
    assert names["flash_attention_fwd.7"]["op_name"].endswith("pallas_call")
    assert names["tuple.9"]["op_name"].endswith("while/body/add")
    assert names["while.8"]["called"] == []


# every case the owner walk has to meet: an async copy pair between two
# named ops, a zero fill read by a named scatter inside a while body, an
# op whose only user is the body's ROOT tuple, a compiler kernel with a
# bare op_name between a named gather and a named multiply, a copy with
# no named user, entry parameters
_OWNED = """HloModule jit_s, is_scheduled=true

%scatter_add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %sum = f32[] add(%a, %b)
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %acc = f32[8]{0} get-tuple-element(%t), index=1
  %c0 = f32[] constant(0)
  %one = s32[] constant(1)
  %zeros = f32[8]{0:T(128)} broadcast(%c0), dimensions={}
  %scatter.1 = f32[8]{0} scatter(%zeros, %i, %acc), update_window_dims={}, to_apply=%scatter_add, metadata={op_name="jit(s)/transpose(jvp(n0))/n0_moe/moe_experts/while/body/scatter-add"}
  %next = s32[] add(%i, %one)
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%next, %scatter.1)
}

%cond (t.c: (s32[], f32[8])) -> pred[] {
  %t.c = (s32[], f32[8]{0}) parameter(0)
  %i.c = s32[] get-tuple-element(%t.c), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(%i.c, %n), direction=LT, metadata={op_name="jit(s)/transpose(jvp(n0))/n0_moe/moe_experts/while/cond/lt"}
}

ENTRY %main (x: f32[8], w: f32[8]) -> (f32[8]) {
  %x = f32[8]{0} parameter(0), sharding={replicated}, metadata={op_name="x"}
  %w = f32[8]{0} parameter(1)
  %gather.3 = f32[8]{0} gather(%x, %w), offset_dims={}, metadata={op_name="jit(s)/jvp(n0)/n0_moe/moe_experts/gather"}
  %ragged-dot-none.4 = f32[8]{0:T(128)} custom-call(%gather.3, %w), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}, backend_config={"custom_call_config":{"body":"eA=="}}
  %multiply.5 = f32[8]{0} multiply(%ragged-dot-none.4, %x), metadata={op_name="jit(s)/jvp(n0)/n0_moe/moe_experts/mul"}
  %copy-start.6 = (f32[8]{0}, f32[8]{0:S(1)}, u32[]{:S(2)}) copy-start(%multiply.5)
  %copy-done.7 = f32[8]{0:S(1)} copy-done(%copy-start.6)
  %add.8 = f32[8]{0} add(%copy-done.7, %w), metadata={op_name="jit(s)/jvp(n0)/n0_out/add"}
  %zero = s32[] constant(0)
  %init = (s32[], f32[8]{0}) tuple(%zero, %add.8)
  %while.9 = (s32[], /*index=1*/f32[8]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(s)/transpose(jvp(n0))/n0_moe/moe_experts/while"}
  %out.10 = f32[8]{0} get-tuple-element(%while.9), index=1
  %copy.11 = f32[8]{0} copy(%out.10)
  ROOT %tuple.12 = (f32[8]{0}) tuple(%copy.11)
}
"""
_S = "jit(s)/transpose(jvp(n0))/n0_moe/moe_experts/while"


@pytest.mark.parametrize("instruction,owner,via", [
    # the async pair between two named ops: made for the reader
    ("copy-start.6", "jit(s)/jvp(n0)/n0_out/add", "user"),
    ("copy-done.7", "jit(s)/jvp(n0)/n0_out/add", "user"),
    # the zero fill of a scatter inside the while's body, and its constant
    ("zeros", _S + "/body/scatter-add", "user"),
    ("c0", _S + "/body/scatter-add", "user"),
    # read only by the body's ROOT: made for the while that runs it
    ("next", _S, "user"),
    ("tuple.2", _S, "user"),
    # the scatter's reducer, through its ROOT to the scatter
    ("sum", _S + "/body/scatter-add", "user"),
    # a compiler kernel with a bare op_name: for the multiply it feeds
    ("ragged-dot-none.4", "jit(s)/jvp(n0)/n0_moe/moe_experts/mul", "user"),
    # the while's operand
    ("init", _S, "user"),
    # no named user (the entry's ROOT): back through the get-tuple-element
    # to the while that made the value
    ("copy.11", _S, "operand"),
    ("out.10", _S, "operand"),
    ("tuple.12", _S, "operand"),
    # entry parameters, named by argument or not, stay without one
    ("x", "", ""),
    ("w", "", ""),
    # named instructions have none
    ("gather.3", "", ""),
    ("while.9", "", ""),
])
def test_every_instruction_with_no_name_gets_an_owner(instruction, owner,
                                                      via):
    names = perf.parse_op_names(_OWNED)
    assert (names[instruction]["owner"], names[instruction]["via"]) \
        == (owner, via)
    assert names["ragged-dot-none.4"]["op_name"] == "ragged-dot-none"


def test_owners_are_found_in_one_walk_over_a_long_chain():
    """A chain of nameless copies: a search per instruction would take
    minutes here; one walk over a users index takes well under a
    second."""
    n = 20000
    lines = ["HloModule jit_s", "", "ENTRY %main (x: f32[8]) -> f32[8] {",
             "  %x = f32[8]{0} parameter(0)",
             "  %copy.0 = f32[8]{0} copy(%x)"]
    lines += [f"  %copy.{i} = f32[8]{{0}} copy(%copy.{i - 1})"
              for i in range(1, n)]
    lines += [f'  ROOT %neg = f32[8]{{0}} negate(%copy.{n - 1}), '
              'metadata={op_name="jit(s)/jvp(n0)/neg"}', "}"]
    names = perf.parse_op_names("\n".join(lines))
    assert all(names[f"copy.{i}"]["owner"] == "jit(s)/jvp(n0)/neg"
               for i in range(n))
    assert names["copy.0"]["via"] == "user"


def test_a_compiled_step_leaves_no_op_without_an_owner():
    """On XLA-CPU's compile of a small step, every instruction with no
    name of the program's gets an owner, but for what holds no work:
    parameters, constants, tuples, get-tuple-elements and bitcasts."""
    _, trainer = _train_one_step()
    text = next(iter(trainer._step._entries.values())).as_text()
    names = perf.op_names(_step_key())
    opcode = dict(re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = (?:\(.*?\)|\S+) "
                             r"([a-z][\w\-]*)\(", text, re.M))
    nameless = [k for k, e in names.items() if "/" not in e["op_name"]
                and not any("/" in c for c in e["called"])]
    assert nameless
    left = [k for k in nameless if not names[k]["owner"]
            and opcode[k] not in ("parameter", "constant", "tuple",
                                  "get-tuple-element", "bitcast")]
    assert not left, [(k, opcode[k]) for k in left]
    assert {names[k]["via"] for k in nameless} <= {"user", "operand", ""}
    assert any(names[k]["via"] == "user" for k in nameless)


def test_a_traced_compile_keeps_its_names_after_its_owner(tracing):
    """A traced benchmark run asks once the loop, and with it the
    trainer, is gone."""
    import gc

    _, trainer = _train_one_step()
    key = _step_key()
    del trainer
    gc.collect()
    names = perf.op_names(key)
    assert names and any("/optimizer/" in n["op_name"]
                         for n in names.values())


@pytest.fixture
def tracing():
    prev = trace.set_enabled(True)
    trace.clear()
    yield
    trace.set_enabled(prev)
    trace.clear()


def test_setup_leaves_its_spans_before_the_first_step(tracing):
    _train_one_step()
    names = [s["name"] for s in trace.spans()]
    first_step = names.index("train.sharded_step")
    for name in ("setup.initialize", "setup.trainer", "capture.trace_lower",
                 "capture.compile"):
        assert names.count(name) == 1, (name, names)
        assert names.index(name) < first_step
    # one per block that deferred its shapes, all before the trainer
    deferred = [i for i, n in enumerate(names) if n == "setup.infer_shape"]
    assert deferred and max(deferred) < names.index("setup.trainer")
    spans = {s["name"]: s for s in trace.spans()}
    lower, compiled = spans["capture.trace_lower"], spans["capture.compile"]
    assert lower["attrs"] == {"label": "sharded_step", "aot_hit": False}
    assert compiled["attrs"]["label"] == "sharded_step"
    assert compiled["attrs"]["cache_hit"] in (True, False)
    # one clock: the spans abut, and together they are compile_ms
    assert lower["t0_ns"] + lower["dur_ns"] == compiled["t0_ns"]
    entry = perf.ledger()[_step_key()]
    assert entry["compile_ms"] == pytest.approx(
        (lower["dur_ns"] + compiled["dur_ns"]) / 1e6)


def test_untraced_nothing_is_recorded_read_or_scoped(monkeypatch):
    assert not trace.enabled()
    trace.clear()

    def refuse(self, *a, **k):
        raise AssertionError("as_text() called with nobody asking")

    monkeypatch.setattr(jax.stages.Compiled, "as_text", refuse)
    monkeypatch.setattr(perf, "parse_op_names", refuse)
    net, trainer = _train_one_step()
    assert trace.spans() == []
    # the eager path opens no scope: a block call outside a
    # functional_call trace, hybridized or not
    def no_scope(name):
        raise AssertionError(f"named_scope({name!r}) on the eager path")

    monkeypatch.setattr(jax, "named_scope", no_scope)
    net(mx.nd.zeros((2, 8, 6))).wait_to_read()
    dense = nn.Dense(3)
    dense.initialize()
    dense.hybridize()
    dense(mx.nd.ones((2, 5))).wait_to_read()


def test_every_captured_executable_is_named_after_its_label():
    from mxnet_tpu import capture

    exe = capture.CapturedExec(lambda x: x * 2.0, label="decode_prefill64",
                               fingerprint="t", sig_argnums=(0,))
    x = jax.numpy.ones((4,))
    np.testing.assert_allclose(np.asarray(exe(x)), 2.0)
    compiled = next(iter(exe._entries.values()))
    assert compiled.as_text().startswith("HloModule jit_decode_prefill64")
    jitted = capture._compile_jit(lambda a, b: a + b, {}, name="sharded_step")
    lowered = jitted.lower(x, x).as_text()
    assert "module @jit_sharded_step" in lowered
