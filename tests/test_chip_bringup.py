"""Bring-up contracts that hold on a host WITHOUT a chip (tier-1, CPU).

chip_smoke.py proves the training path on the v5e; these tests pin what
must stay true here: measurement entry points fail without a chip
instead of falling back, accelerator contexts never resolve to the CPU,
the default context follows jax's default backend, kvstore('tpu') sums
across devices, a process fleet is refused by a parent that holds the
chip, there is ONE placeable and bounded compile cache, and the peaks
table never lends one device another's number.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd=REPO, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "JAX_ENABLE_COMPILATION_CACHE", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_over)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_measurement_entry_points_fail_without_a_chip(script):
    r = _run([script])
    assert r.returncode != 0
    assert r.stdout.strip() == "", "no result may be printed without a chip"
    assert "no chip" in r.stderr or "no accelerator" in r.stderr, r.stderr
    assert "cpu" in r.stderr


def test_every_bench_mode_requires_the_chip(monkeypatch):
    """Each mode of bench.py and the tools benches asks for the chip
    before any work (in-process: the refusal is the first thing each
    does); a mode bench.py does not have is an error, not the default."""
    import importlib

    monkeypatch.syspath_prepend(os.path.join(REPO, "tools"))
    monkeypatch.syspath_prepend(REPO)
    bench = importlib.import_module("bench")
    for mode in (bench.main, lambda: bench.main(capture_mode=True),
                 bench.main_transformer, bench.main_dist,
                 lambda: importlib.import_module("serving_bench").main([]),
                 lambda: importlib.import_module("bench_int8").main([])):
        with pytest.raises(mx.MXNetError, match="no accelerator"):
            mode()
    r = _run(["bench.py", "--data=stream"])
    assert r.returncode != 0 and r.stdout == ""
    assert "unknown argument" in r.stderr


def test_accelerator_context_never_resolves_to_the_cpu():
    for ctx in (mx.tpu(), mx.gpu()):
        with pytest.raises(mx.MXNetError, match="no accelerator"):
            ctx.jax_device()
    with pytest.raises(mx.MXNetError, match="no accelerator"):
        mx.nd.zeros((2,), ctx=mx.tpu()).asnumpy()


def test_default_context_follows_the_default_backend():
    import jax

    assert jax.default_backend() == "cpu"
    assert mx.current_context() == mx.cpu(0)
    assert mx.nd.zeros((2,)).context == mx.cpu(0)
    with mx.cpu(1):
        assert mx.current_context() == mx.cpu(1)
    # the rule itself, seen from a process whose default backend is not
    # the cpu: the default context is the accelerator's first device
    from mxnet_tpu import context

    class _TpuJax:
        @staticmethod
        def default_backend():
            return "tpu"

    real, context._jax = context._jax, lambda: _TpuJax
    try:
        assert mx.current_context() == mx.tpu(0)
    finally:
        context._jax = real


def test_cpu_context_names_a_missing_cpu_backend(monkeypatch):
    import jax

    def no_cpu(backend=None, **_):
        raise RuntimeError("Unknown backend cpu")

    monkeypatch.setattr(jax, "local_devices", no_cpu)
    with pytest.raises(mx.MXNetError, match="cpu backend is not initialised"):
        mx.cpu(0).jax_device()


def test_kvstore_tpu_sums_values_committed_to_four_devices():
    ctxs = [mx.cpu(i) for i in range(4)]
    kv = mx.kvstore.create("tpu")
    kv.init("w", mx.nd.zeros((3,)))
    vals = [mx.nd.ones((3,), ctx=c) * (i + 1) for i, c in enumerate(ctxs)]
    assert len({next(iter(v.data_.devices())) for v in vals}) == 4
    kv.push("w", vals)
    outs = [mx.nd.zeros((3,), ctx=c) for c in ctxs]
    kv.pull("w", out=outs)
    for c, o in zip(ctxs, outs):
        assert np.array_equal(o.asnumpy(), np.full((3,), 10.0, np.float32))
        # each puller holds the sum on ITS device, not a view of device 0
        assert o.data_.devices() == {c.jax_device()}


def test_process_fleet_refuses_a_parent_that_holds_the_chip(monkeypatch):
    """However the parent opened the chip (a Context or jax directly),
    a process replica is refused at once, not spawned to fail."""
    import jax

    from mxnet_tpu.serving import fleet

    jax.devices()  # this process has initialised its backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    replica = fleet._ProcessReplica("m", 0, lambda: None, {}, None)
    with pytest.raises(mx.MXNetError, match="already holds the tpu"):
        replica.build()
    assert replica._proc is None


_CACHE_PROBE = ("import jax, mxnet_tpu; c = jax.config; "
                "print(c.jax_compilation_cache_dir, "
                "c.jax_persistent_cache_min_compile_time_secs)")
_FEW_OPS = ("; import mxnet_tpu as mx; x = mx.nd.ones((3,)); "
            "[f(x).asnumpy() for f in (mx.nd.exp, mx.nd.sqrt, mx.nd.tanh, "
            "mx.nd.sigmoid, mx.nd.relu, mx.nd.abs)]")


def test_compile_cache_is_checkout_local_by_default():
    r = _run(["-c", _CACHE_PROBE])
    assert r.returncode == 0, r.stderr
    # pinned to XLA-CPU: jax's own compile-time floor stays (its loader
    # logs an error per hit, and CPU compiles are cheap)
    assert r.stdout.split() == [os.path.join(REPO, ".jax_cache"), "1.0"]
    # not pinned (what a chip host sees): every executable is kept
    r = _run(["-c", _CACHE_PROBE], JAX_PLATFORMS="")
    assert r.returncode == 0, r.stderr
    assert r.stdout.split()[1] == "0.0"


def test_default_compile_cache_is_held_under_the_size_cap(tmp_path):
    """<checkout>/.jax_cache is swept to MXNET_TPU_COMPILE_CACHE_MAX_MB
    when the process ends (seen through a symlinked checkout, so the
    sweep never touches this checkout's own cache)."""
    os.symlink(os.path.join(REPO, "mxnet_tpu"), tmp_path / "mxnet_tpu")
    cache = tmp_path / ".jax_cache"

    r = _run(["-c", _CACHE_PROBE + _FEW_OPS], cwd=tmp_path,
             JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
             MXNET_TPU_COMPILE_CACHE_MAX_MB="0.005")
    assert r.returncode == 0, r.stderr
    assert r.stdout.split()[0] == str(cache)
    # the eight-odd executables written are ~2.5 KB each
    assert 0 < sum(f.stat().st_size for f in cache.iterdir()) <= 5000


def test_compile_cache_honours_jax_compilation_cache_dir(tmp_path):
    placed = str(tmp_path / "placed")
    r = _run(["-c", _CACHE_PROBE + "; import mxnet_tpu as mx; "
              "(mx.nd.ones((3,)) + 1).asnumpy()"],
             JAX_COMPILATION_CACHE_DIR=placed, MXNET_TPU_COMPILE_CACHE="",
             JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert r.returncode == 0, r.stderr
    assert r.stdout.split()[0] == placed
    assert os.listdir(placed), "the placed cache received nothing"
    # ... and nothing else was configured or created beside it
    assert os.listdir(tmp_path) == ["placed"]


def test_peaks_table_never_borrows_another_devices_number():
    from mxnet_tpu.observability import perf

    assert perf.device_peaks("TPU v5 lite") == {
        "bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9}
    with pytest.raises(KeyError, match="TPU v99"):
        perf.device_peaks("TPU v99")
    assert perf.nominal_peaks("TPU v99") == (None, None)
    with pytest.raises(mx.MXNetError, match="no accelerator"):
        perf.require_chip()
