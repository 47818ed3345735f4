"""Activation rematerialization (gradient mirroring) tests.

Reference: MXNET_BACKWARD_DO_MIRROR (src/executor/graph_executor.cc:357),
mirror pass src/nnvm/gradient.cc:107-148. TPU-native form: jax.checkpoint
around the traced forward (mxnet_tpu/remat.py).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon import nn


def _small_net(seed=0):
    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1))
    net.add(nn.BatchNorm())
    net.add(nn.Activation("relu"))
    net.add(nn.Conv2D(8, 3, padding=1))
    net.add(nn.BatchNorm())
    net.add(nn.Activation("relu"))
    net.add(nn.Dense(4))
    net.initialize(mx.initializer.Xavier(rnd_type="gaussian"))
    return net


def _copy_net(dst, src):
    # pair by registration order (same structure); name-sorting breaks once
    # auto-naming counters pass 9 (conv10 < conv2 lexicographically)
    for (kd, pd), (ks, ps) in zip(dst.collect_params().items(),
                                  src.collect_params().items()):
        assert tuple(pd.shape) == tuple(ps.shape), (kd, ks)
        pd.set_data(ps.data())


def test_sharded_trainer_remat_matches_exact():
    import jax

    mesh = parallel.create_mesh({"dp": 1}, jax.devices("cpu")[:1])
    rng = np.random.RandomState(0)
    x = rng.rand(4, 3, 8, 8).astype(np.float32)
    y = (rng.rand(4) * 4).astype(np.float32)

    losses = []
    params_after = []
    for remat in (False, True):
        net = _small_net()
        net(mx.nd.zeros((2, 3, 8, 8)))
        if remat:
            _copy_net(net, ref_net)
        else:
            ref_net = net
        tr = parallel.ShardedTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1}, mesh=mesh, remat=remat)
        loss = tr.step(x, y)
        losses.append(float(np.asarray(loss)))
        params_after.append({k: np.asarray(v) for k, v in tr.params.items()})

    assert np.allclose(losses[0], losses[1], rtol=1e-5)
    for (k0, v0), (k1, v1) in zip(params_after[0].items(),
                                  params_after[1].items()):
        np.testing.assert_allclose(v0, v1, rtol=1e-4, atol=1e-5,
                                   err_msg=f"{k0}/{k1} diverged under remat")


def test_remat_recomputes_in_backward():
    """The remat backward must contain more conv applications than the
    exact backward (recompute), proving checkpoint is actually applied."""
    import jax

    net = _small_net()
    net(mx.nd.zeros((2, 3, 8, 8)))
    fwd = parallel.functional_call(net, train=True)
    params = parallel.param_arrays(net)
    aux = parallel.aux_arrays(net)
    x = np.zeros((4, 3, 8, 8), np.float32)

    def count_convs(f):
        def loss(p):
            out, _ = f(p, aux, x)
            return out.sum().astype(np.float32)
        jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
        return str(jaxpr).count("conv_general_dilated")

    n_exact = count_convs(fwd)
    n_remat = count_convs(jax.checkpoint(fwd))
    assert n_remat > n_exact, (n_exact, n_remat)


def test_executor_mirror_env_grads_match(monkeypatch):
    data = mx.sym.var("data")
    w = mx.sym.var("w")
    h = mx.sym.FullyConnected(data, w, num_hidden=8, no_bias=True)
    h = mx.sym.Activation(h, act_type="tanh")
    out = mx.sym.sum(h * h)

    rng = np.random.RandomState(1)
    args = {"data": mx.nd.array(rng.rand(3, 5)),
            "w": mx.nd.array(rng.rand(8, 5))}

    grads = []
    for flag in ("0", "1"):
        monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", flag)
        g = {"data": mx.nd.zeros((3, 5)), "w": mx.nd.zeros((8, 5))}
        ex = out.bind(mx.cpu(), args, args_grad=g)
        ex.forward(is_train=True)
        ex.backward(mx.nd.ones(ex.outputs[0].shape))
        grads.append({k: v.asnumpy() for k, v in g.items()})
    for k in grads[0]:
        np.testing.assert_allclose(grads[0][k], grads[1][k], rtol=1e-5,
                                   atol=1e-6)


def test_remat_block_matches_plain():
    """gluon.contrib.Remat is numerically transparent inside a trainer."""
    import jax

    mesh = parallel.create_mesh({"dp": 1}, jax.devices("cpu")[:1])
    rng = np.random.RandomState(0)
    x = rng.rand(4, 3, 8, 8).astype(np.float32)
    y = (rng.rand(4) * 4).astype(np.float32)

    results = []
    ref_net = None
    for wrap in (False, True):
        inner = _small_net()
        inner(mx.nd.zeros((2, 3, 8, 8)))
        if wrap:
            _copy_net(inner, ref_net)
            net = gluon.contrib.Remat(inner)
        else:
            ref_net = inner
            net = inner
        tr = parallel.ShardedTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1}, mesh=mesh)
        loss = tr.step(x, y)
        results.append(float(np.asarray(loss)))
    assert np.allclose(results[0], results[1], rtol=1e-5), results


def test_remat_block_eager_passthrough():
    inner = _small_net()
    net = gluon.contrib.Remat(inner)
    x = mx.nd.array(np.random.RandomState(0).rand(2, 3, 8, 8))
    out = net(x)
    ref = inner(x)
    np.testing.assert_allclose(out.asnumpy(), ref.asnumpy(), rtol=1e-6)


def test_resolve_policy():
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    from mxnet_tpu.remat import KERNEL_RESIDUAL, policy_name, resolve_policy

    def sines(spec, name):
        def f(x):
            return jnp.sum(checkpoint_name(jnp.sin(x), name) ** 2)

        return str(jax.make_jaxpr(jax.grad(jax.checkpoint(
            f, policy=resolve_policy(spec))))(jnp.ones(3))).count(" sin ")

    # the default keeps what carries the kernels' name and nothing else
    for spec in (True, None):
        assert sines(spec, KERNEL_RESIDUAL) == 1
        assert sines(spec, "another_name") == 2
        assert policy_name(spec) == f"save_only_these_names({KERNEL_RESIDUAL})"
    assert sines("nothing_saveable", KERNEL_RESIDUAL) == 2
    p = resolve_policy("dots_with_no_batch_dims_saveable")
    assert callable(p) and resolve_policy(p) is p
    assert policy_name("nothing_saveable") == "nothing_saveable"
    assert policy_name(p) == p.__name__
    with pytest.raises(ValueError):
        resolve_policy("not_a_policy")
    with pytest.raises(TypeError):
        resolve_policy(3)


# A Remat half around each hand-written kernel (interpret mode): a
# projection, the kernel, an elementwise pass and a projection, so that
# the backward needs the kernel's result (the tanh's input) as well as
# what the backward kernel reads.
KERNELS = ("flash", "flash_window", "flash_lse", "delta_rule")


def _kernel_half(kind):
    import jax.numpy as jnp

    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.ops import delta_rule_kernels, pallas_kernels

    width = 256 if kind == "delta_rule" else 64

    class Half(gluon.HybridBlock):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.proj = nn.Dense(width, flatten=False, use_bias=False)
                self.out = nn.Dense(8, flatten=False, use_bias=False)

        def forward(self, x):
            h = self.proj(x).data_
            t = h.shape[1]
            if kind == "delta_rule":
                qk = h[..., :128].reshape(1, t, 1, 128)
                v = h.reshape(1, t, 2, 128)
                g = -jnp.abs(h[..., :2])
                o = delta_rule_kernels.gated_delta_rule_kernels(
                    qk, qk + 0.5, v, g, jnp.exp(g), interpret=True)
                o = o.reshape(1, t, width)
            else:
                q = h.reshape(1, t, 2, 32).transpose(0, 2, 1, 3)
                args = (q, q * 0.5, q + 1.0)
                if kind == "flash_lse":
                    o, lse = pallas_kernels.flash_attention_with_lse(
                        *args, causal=True, interpret=True)
                    o = o * jnp.tanh(lse)
                else:
                    o = pallas_kernels.flash_attention_with_grad(
                        *args, causal=True, interpret=True,
                        window=48 if kind == "flash_window" else None)
                o = o.transpose(0, 2, 1, 3).reshape(1, t, width)
            return self.out(NDArray(jnp.tanh(o)))

    return Half()


@pytest.fixture(scope="module")
def remat_halves():
    """kind -> policy -> (text of the gradient's jaxpr, the gradients),
    each computed once for the tests below."""
    import jax
    import jax.numpy as jnp

    cache = {}

    def get(kind, policy):
        if (kind, policy) not in cache:
            mx.random.seed(7)
            inner = _kernel_half(kind)
            inner.initialize(mx.initializer.Xavier(rnd_type="gaussian"))
            x = np.random.RandomState(0).rand(1, 128, 16).astype(np.float32)
            inner(mx.nd.array(x))
            net = gluon.contrib.Remat(inner, policy=policy)
            fwd = parallel.functional_call(net, train=True)
            params = parallel.param_arrays(net)
            aux = parallel.aux_arrays(net)

            def loss(p):
                out, _ = fwd(p, aux, x)
                return jnp.sum(out.astype(jnp.float32) ** 2)

            grad = jax.grad(loss)
            cache[kind, policy] = (
                str(jax.make_jaxpr(grad)(params)),
                [np.asarray(g) for g in jax.tree_util.tree_leaves(
                    grad(params))])
        return cache[kind, policy]

    return get


@pytest.mark.parametrize("kind", KERNELS)
def test_remat_default_runs_a_kernel_once(remat_halves, kind):
    """Under Remat's default the gradient's program holds the forward
    kernel once: what its backward kernel reads was kept. Under
    ``'nothing_saveable'`` it holds it twice, as it did before the
    kernels named their residuals."""
    name = "name=" + ("gated_delta_rule_fwd" if kind == "delta_rule"
                      else "flash_attention_fwd")
    assert remat_halves(kind, None)[0].count(name) == 1
    assert remat_halves(kind, "nothing_saveable")[0].count(name) == 2


@pytest.mark.parametrize("kind", KERNELS)
def test_remat_default_gradients_are_the_recomputed_ones(remat_halves, kind):
    """A kept result is the value the second run would have produced:
    the two policies' gradients are equal bit for bit."""
    kept = remat_halves(kind, None)[1]
    again = remat_halves(kind, "nothing_saveable")[1]
    assert len(kept) == len(again) == 2
    for a, b in zip(kept, again):
        assert np.any(a != 0)
        np.testing.assert_array_equal(a, b)


def _conv_half():
    """A Remat half around the short convolution's kernels (interpret
    mode): a projection, the convolution + SiLU handed on in two parts
    and the columns past them, an elementwise pass, a projection."""
    import jax.numpy as jnp

    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.ops import conv_silu_kernels

    class Half(gluon.HybridBlock):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.proj = nn.Dense(512, flatten=False, use_bias=False)
                self.conv_weight = self.params.get(
                    "conv_weight", shape=(384, 4),
                    init=mx.initializer.Uniform(0.5))
                self.out = nn.Dense(8, flatten=False, use_bias=False)

        def forward(self, x):
            parts = conv_silu_kernels.causal_conv_silu_kernels(
                self.proj(x).data_, self.conv_weight.data().data_,
                (128, 256), interpret=True)
            return self.out(NDArray(jnp.tanh(
                jnp.concatenate(parts, axis=-1))))

    return Half()


def test_remat_default_runs_the_short_convolution_again():
    """One pass over its input, cheaper to run again than to keep: the
    forward kernel names no residual, so under Remat's default a half's
    gradient holds it twice a part (forward, recomputation) and the
    backward kernel once, exactly the program ``'nothing_saveable'``
    gives; nothing of the forward's results is kept."""
    import jax
    import jax.numpy as jnp

    texts = {}
    for policy in (None, "nothing_saveable"):
        mx.random.seed(7)
        inner = _conv_half()
        inner.initialize(mx.initializer.Xavier(rnd_type="gaussian"))
        x = np.random.RandomState(0).rand(1, 48, 16).astype(np.float32)
        inner(mx.nd.array(x))
        net = gluon.contrib.Remat(inner, policy=policy)
        fwd = parallel.functional_call(net, train=True)
        params = parallel.param_arrays(net)
        aux = parallel.aux_arrays(net)

        def loss(p):
            out, _ = fwd(p, aux, x)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        texts[policy] = str(jax.make_jaxpr(jax.grad(loss))(params))
        assert all(np.any(np.asarray(g) != 0) for g in
                   jax.tree_util.tree_leaves(jax.grad(loss)(params)))
    kept = texts[None]
    assert kept.count("name=causal_conv_silu_fwd") == 2 * 2
    assert kept.count("name=causal_conv_silu_bwd") == 2
    assert "kernel_residual" not in kept
    # the same program but for the policy's own name in the jaxpr
    import re

    def policy_less(text):
        return re.sub(r"policy=<function .*>", "policy=_", text)

    assert policy_less(kept) == policy_less(texts["nothing_saveable"])


@pytest.mark.parametrize("entry", ["with_grad", "with_lse"])
def test_residual_names_lower_to_nothing_outside_checkpoint(monkeypatch,
                                                            entry):
    """No ``jax.checkpoint`` around it (GPT-2's cell): the lowered
    forward + backward of a flash call is the text it is without the
    names, but for the counters in private functions' symbols (jax
    emits each distinct equation as a private function before it
    inlines it, ``name`` too, and a second one of another shape moves
    the symbol table's counter on by one)."""
    import re

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels

    def loss(q, k, v):
        if entry == "with_grad":
            outs = [pallas_kernels.flash_attention_with_grad(
                q, k, v, causal=True, interpret=True)]
        else:
            outs = pallas_kernels.flash_attention_with_lse(
                q, k, v, causal=True, interpret=True)
        return sum(jnp.sum(o.astype(jnp.float32) ** 2) for o in outs)

    q = jnp.zeros((1, 2, 128, 32), jnp.float32)

    def lowered():
        return re.sub(r"(@\w+?)_\d+\b", r"\1", jax.jit(jax.grad(
            loss, argnums=(0, 1, 2))).lower(q, q, q).as_text())

    named = lowered()
    assert "kernel_residual" in str(jax.make_jaxpr(jax.grad(loss))(q, q, q))
    monkeypatch.setattr(pallas_kernels, "kernel_residuals",
                        lambda *values: values)
    assert "kernel_residual" not in str(
        jax.make_jaxpr(jax.grad(loss))(q, q, q))
    assert lowered() == named


def test_remat_records_a_trace_span():
    """One ``remat.trace`` span each time a block is traced under
    ``jax.checkpoint``, naming the block and the policy; none from the
    eager pass-through."""
    import jax

    from mxnet_tpu.observability import trace
    from mxnet_tpu.remat import KERNEL_RESIDUAL

    was = trace.enabled()
    trace.set_enabled(True)
    try:
        trace.clear()
        inner = _small_net()
        x = np.zeros((2, 3, 8, 8), np.float32)
        inner(mx.nd.array(x))
        for policy, name in ((None,
                              f"save_only_these_names({KERNEL_RESIDUAL})"),
                             ("nothing_saveable", "nothing_saveable")):
            net = gluon.contrib.Remat(inner, policy=policy)
            net(mx.nd.array(x))
            assert not trace.spans(name="remat.trace")
            fwd = parallel.functional_call(net, train=True)
            fwd(parallel.param_arrays(net), parallel.aux_arrays(net), x)
            assert not trace.spans(name="remat.trace")  # no tracer: eager
            jax.make_jaxpr(fwd)(parallel.param_arrays(net),
                                parallel.aux_arrays(net), x)
            (span,) = trace.spans(name="remat.trace")
            assert span["dur_ns"] == 0
            assert span["attrs"] == {"block": inner.name, "policy": name}
            trace.clear()
    finally:
        trace.set_enabled(was)
        trace.clear()
