"""Kernel autotuning: schedule registry, legalization, measured search,
table persistence, AOT-fingerprint interaction, and the demo contract
(marker: tune; docs/autotune.md).

The safety properties under test:
- numerics: flash attention is numerically identical (fwd + grad,
  causal and not) across legal schedule candidates, and the search
  driver REJECTS a candidate whose outputs disagree — tuning can never
  change results;
- tails: a backward block that does not divide T pads and masks
  instead of silently dropping the tail (regression: odd T);
- identity: a schedule-table change re-keys the AOT compile cache (no
  stale artifact hit), an unchanged table reuses the cached executable
  bit-for-bit.
"""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401  (fixes the jax platform first)
from mxnet_tpu import capture, tune
from mxnet_tpu.tune import measure, schedule, search

pytestmark = pytest.mark.tune

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ legalization

def test_legalize_block_rules():
    # divisor on the sublane grid, largest first
    assert schedule.legalize_block(256, 128) == 128
    assert schedule.legalize_block(256, 64) == 64
    assert schedule.legalize_block(192, 128) == 96
    assert schedule.legalize_block(200, 128) == 40
    # single block covers any length when the cap allows
    assert schedule.legalize_block(65, 128) == 65
    assert schedule.legalize_block(4, 128) == 4
    # no legal block: T > cap and no sublane divisor
    assert schedule.legalize_block(130, 128) is None
    assert schedule.legalize_block(0, 128) is None


def test_legal_flash_blocks_subset():
    assert schedule.legal_flash_blocks(256) == [256, 128, 64, 32, 16, 8]
    assert schedule.legal_flash_blocks(96) == [96, 32, 16, 8]
    assert 65 in schedule.legal_flash_blocks(65)  # single block only
    assert schedule.legal_flash_blocks(65)[1:] == []


def test_flash_shape_supported_gate():
    assert schedule.flash_shape_supported(256, 64)
    assert schedule.flash_shape_supported(65, 64)   # single block
    assert not schedule.flash_shape_supported(130, 64)
    assert not schedule.flash_shape_supported(256, 512)  # D > 256


def test_explicit_override_must_divide():
    with pytest.raises(ValueError):
        schedule.flash_fwd_blocks(2, 256, 32, "float32", interpret=True,
                                  block_q=48)
    # divides T but sits OFF the sublane grid: must fail at the
    # ScheduleError boundary, not deep inside Mosaic on a chip
    with pytest.raises(ValueError):
        schedule.flash_fwd_blocks(2, 200, 32, "float32", interpret=True,
                                  block_q=25)
    # the single-block exception applies to overrides too
    assert schedule.flash_fwd_blocks(
        1, 65, 32, "float32", interpret=True,
        block_q=65, block_k=65) == (65, 65)
    assert schedule.flash_fwd_blocks(
        2, 256, 32, "float32", interpret=True,
        block_q=64, block_k=32) == (64, 32)


def test_widened_flash_axes_keep_the_short_sequences():
    """The candidate axes reach 1024; what is legal at T <= 256 is what
    it was, and a wide tile validates as a table value."""
    assert schedule.legal_flash_blocks(1024)[:4] == [1024, 512, 256, 128]
    assert schedule.legal_flash_blocks(512)[:2] == [512, 256]
    assert schedule.legal_flash_blocks(256) == [256, 128, 64, 32, 16, 8]
    assert set(schedule.SEARCH_SPACE["flash_fwd"]) == {"block_q", "block_k"}
    assert schedule.SEARCH_SPACE["flash_bwd"] == \
        schedule.SEARCH_SPACE["flash_fwd"]
    wide = {"schema_version": 1, "entries": {
        "flash_fwd|tpu|bfloat16|bh128-t1024-d64": {
            "schedule": {"block_q": 1024, "block_k": 512}}}}
    assert schedule.validate_table(wide) == []


@pytest.mark.parametrize("t,d,dtype,itemsize", [
    (65, 64, "float32", 4), (96, 32, "float32", 4), (200, 32, "bfloat16", 2),
    (1024, 64, "bfloat16", 2), (1024, 256, "float32", 4),
    (1024, 256, "bfloat16", 2), (4096, 128, "bfloat16", 2)])
def test_default_flash_schedule_is_legal_by_shape(t, d, dtype, itemsize,
                                                  monkeypatch):
    """A shape without a table entry gets a legal tile that is worth a
    grid step and fits VMEM at its D and dtype: it depends on what the
    builder sees, not on a model's name."""
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "0")   # no table: the default
    bq, bk = schedule.flash_fwd_blocks(16, t, d, dtype, interpret=True)
    for b in (bq, bk):
        assert t % b == 0 and (b == t or b % schedule.MIN_SUBLANE == 0)
    assert (bq, bk) == (min(t, 512), min(t, 512))
    # a step is filled up to one 512 x 512 tile: one head of it, more of less
    assert (schedule.flash_fwd_heads(16, bq, bk, d, itemsize) == 1) == (
        2 * bq * bk > schedule.FLASH_STEP_SCORES)
    hb = schedule.flash_fwd_heads(16, bq, bk, d, itemsize)
    assert hb in schedule.FLASH_HEAD_CANDIDATES and 16 % hb == 0
    assert hb * bq * bk <= schedule.FLASH_STEP_SCORES or hb == 1
    assert schedule.flash_fwd_vmem_bytes(hb, bq, bk, d, itemsize) \
        <= schedule.FLASH_VMEM_BUDGET
    assert schedule.flash_fwd_vmem_limit(hb, bq, bk, d, itemsize) is None
    # an odd head count still gets a divisor
    assert 6 % schedule.flash_fwd_heads(6, bq, bk, d, itemsize) == 0


def test_flash_vmem_limit_rises_with_the_tile():
    """A tile over the compiler's scoped default asks for its own
    limit, under the ceiling (1024 x 1024 in float32 at D = 256)."""
    need = schedule.flash_fwd_vmem_bytes(1, 1024, 1024, 256, 4)
    assert need > schedule.FLASH_VMEM_BUDGET
    limit = schedule.flash_fwd_vmem_limit(1, 1024, 1024, 256, 4)
    assert need < limit <= schedule.FLASH_VMEM_CEILING
    assert schedule.flash_fwd_heads(16, 1024, 1024, 256, 4) == 1


# (BH, T, D, dtype, itemsize, q windows): the two training cells'
# attention, short and off-tile sequences, the widest head in float32,
# and sequences whose dq no longer fits VMEM in one window
@pytest.mark.parametrize("bh,t,d,dtype,itemsize,windows", [
    (8, 65, 64, "float32", 4, 1), (8, 200, 32, "bfloat16", 2, 1),
    (128, 1024, 64, "bfloat16", 2, 1), (16, 8192, 256, "bfloat16", 2, 1),
    (16, 1024, 256, "float32", 4, 1), (16, 16384, 256, "bfloat16", 2, 2),
    (16, 65536, 128, "bfloat16", 2, 4), (16, 65536, 128, "float32", 4, 8)])
def test_default_flash_bwd_schedule_is_legal_by_shape(
        bh, t, d, dtype, itemsize, windows, monkeypatch):
    """The backward's tile, heads a step and q windows follow from what
    the builder sees, (T, D, dtype), legalized as the forward's are but on
    the lane grid: every shape the forward takes has a backward tile that
    fits VMEM (a long sequence off the lane grid runs padded to it)."""
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "0")   # no table: the default
    bq, bk = schedule.flash_bwd_block(bh, t, d, dtype, interpret=True)
    assert (bq, bk) == schedule.flash_fwd_blocks(bh, t, d, dtype,
                                                 interpret=True)
    n_win = schedule.flash_bwd_windows(t, bq, bk, d, itemsize)
    assert n_win == windows and (t // bq) % n_win == 0
    rows = t // n_win
    hb = schedule.flash_bwd_heads(bh, bq, bk, rows, d, itemsize)
    assert bh % hb == 0 and (hb * bq * bk <= schedule.FLASH_STEP_SCORES
                             or hb == 1)
    need = schedule.flash_bwd_vmem_bytes(hb, bq, bk, rows, d, itemsize)
    limit = schedule.flash_bwd_vmem_limit(hb, bq, bk, rows, d, itemsize)
    if limit is None:
        assert need <= schedule.FLASH_VMEM_BUDGET
    else:
        assert need < limit <= schedule.FLASH_VMEM_CEILING
        assert 3 * need <= 2 * schedule.FLASH_VMEM_CEILING
    assert schedule.flash_bwd_length(t) == t
    assert all(b == t or b % schedule.LANES == 0 for b in (bq, bk))
    # overrides are legalized onto the same grid: too wide is T, under
    # a lane tile is one lane tile (or T, where that is all there is)
    assert schedule.flash_bwd_block(bh, t, d, dtype, block_k=t + 8,
                                    block_q=bq) == (bq, t)
    assert schedule.flash_bwd_block(bh, t, d, dtype, block_k=64,
                                    block_q=bq) == (
        bq, schedule.LANES if t % schedule.LANES == 0 else t)
    assert schedule.flash_bwd_length(2000) == 2048
    assert schedule.flash_bwd_block(4, 2048, 64, dtype,
                                    interpret=True) == (512, 512)


def test_flash_bwd_workload_and_table_entry_steer_the_backward(
        tmp_path, monkeypatch):
    """``flash_bwd_workload`` sweeps (block_q, block_k) of the backward
    kernel at its dtype and keys the entry by it; the entry it writes is
    the one the builder's lookup hits."""
    import jax.numpy as jnp

    wl = search.flash_bwd_workload(b=1, h=2, t=256, d=16, interpret=True,
                                   quick=True, dtype="bfloat16")
    assert wl.dtype == "bfloat16" and wl.kernel == "flash_bwd"
    fn, args = wl.build(wl.reference())
    # q, k, v (sequence-minor), out and dout in the dtype; lse float32
    assert [a.dtype for a in args] == [jnp.bfloat16] * 4 + [
        jnp.float32, jnp.bfloat16]
    tbl = str(tmp_path / "t.json")
    res = search.run_search(wl, tbl, rounds=1, iters=1)
    assert res["key"] == "flash_bwd|interpret|bfloat16|bh2-t256-d16"
    # tiles on the lane grid: 128 and the whole of T
    assert res["rejected"] == 0 and res["candidates"] == 4
    won = schedule.load_single_table(tbl)[res["key"]]["schedule"]
    assert set(won) == {"block_q", "block_k"}
    monkeypatch.setenv("MXNET_TPU_SCHEDULE_TABLE", tbl)
    tune.reset_stats()
    assert schedule.flash_bwd_block(2, 256, 16, "bfloat16",
                                    interpret=True) == (
        won["block_q"], won["block_k"])
    assert tune.stats()["autotune_table_hits"] == 1
    # the production sweeps leave the narrow tiles out, and the one
    # block of a long T
    wide = search.flash_bwd_workload(b=1, h=1, t=1024, d=16,
                                     interpret=True, min_block=256)
    assert len(wide.candidates()) == 9
    long = search.flash_bwd_workload(b=1, h=1, t=8192, d=16,
                                     interpret=True, min_block=256)
    assert len(long.candidates()) == 9


@pytest.mark.parametrize("kernel,bh,t,d,candidates", [
    ("flash_fwd", 8 * 16, 1024, 64, 16), ("flash_bwd", 8 * 16, 1024, 64, 9),
    ("flash_bwd", 16, 8192, 256, 9)])
def test_committed_tpu_entries_resolve_for_the_training_cells(
        kernel, bh, t, d, candidates, monkeypatch):
    """tools/schedule_table.json carries the chip-measured entries of
    the training cells' attention (ROADMAP A5): GPT-2 medium's forward
    and backward, Qwen3-Next's backward. On a TPU backend the builder's
    lookup hits them."""
    monkeypatch.delenv("MXNET_TPU_SCHEDULE_TABLE", raising=False)
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "1")
    key = schedule.entry_key(kernel, schedule.flash_shape_key(bh, t, d),
                             "bfloat16", "tpu")
    assert key == f"{kernel}|tpu|bfloat16|bh{bh}-t{t}-d{d}"
    entry = schedule.load_single_table(schedule.default_table_path())[key]
    assert schedule.validate_table(
        {"schema_version": 1, "entries": {key: entry}}) == []
    # its paired measurements: the winner against the reference schedule
    assert 0 < entry["measured_ms"] <= entry["ref_ms"]
    assert entry["candidates"] >= candidates and entry["tuned_at"]
    monkeypatch.setattr(schedule, "resolve_backend",
                        lambda interpret=False: "tpu")
    tune.reset_stats()
    resolve = {"flash_fwd": schedule.flash_fwd_blocks,
               "flash_bwd": schedule.flash_bwd_block}[kernel]
    sched = entry["schedule"]
    assert resolve(bh, t, d, "bfloat16") == (
        sched["block_q"], sched["block_k"])
    assert tune.stats()["autotune_table_hits"] == 1
    assert tune.stats()["autotune_table_misses"] == 0
    # float32 inputs at the same shape have no entry: the default
    assert resolve(bh, t, d, "float32") == (512, 512)
    assert tune.stats()["autotune_table_misses"] == 1


# (value heads over the batch, T, chunks of the sequence, Dk, Dv, dtype,
# itemsize, key heads' group): the Qwen3-Next cell's, sequences of a
# prime number of chunks and of one, wide heads in float32
@pytest.mark.parametrize("bh,t,n,dk,dv,dtype,itemsize,rep", [
    (32, 8192, 128, 128, 128, "bfloat16", 2, 2),
    (4, 150, 3, 128, 256, "float32", 4, 2),
    (2, 64, 1, 128, 128, "bfloat16", 2, 1),
    (8, 832, 13, 256, 256, "float32", 4, 4),
    (16, 65536, 1024, 128, 128, "bfloat16", 2, 1)])
@pytest.mark.parametrize("kernel", ["delta_rule_fwd", "delta_rule_bwd"])
def test_default_delta_rule_schedule_is_legal_by_shape(
        kernel, bh, t, n, dk, dv, dtype, itemsize, rep, monkeypatch):
    """Chunks a grid step follow from what the builder sees: the
    default's, cut to a divisor of the sequence's chunks, a step that
    fits VMEM under the limit the kernel asks for; the caller's override
    is legalized the same way."""
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "0")   # no table: the default
    assert schedule.delta_rule_shape_supported(dk, dv, 64)
    nb = schedule.delta_rule_chunks(kernel, bh, t, n, dk, dv, dtype,
                                    interpret=True)
    want = schedule.DEFAULT_SCHEDULES[kernel]["chunks"]
    assert 1 <= nb <= want and n % nb == 0
    assert nb == max(c for c in range(1, want + 1) if n % c == 0)
    need = schedule.delta_rule_vmem_bytes(kernel, nb, 64, rep, dk, dv,
                                          itemsize)
    limit = schedule.delta_rule_vmem_limit(kernel, nb, 64, rep, dk, dv,
                                           itemsize)
    if limit is None:
        assert need <= schedule.FLASH_VMEM_BUDGET
    else:
        assert need < limit <= schedule.FLASH_VMEM_CEILING
    for asked in schedule.SEARCH_SPACE[kernel]["chunks"]:
        got = schedule.delta_rule_chunks(kernel, bh, t, n, dk, dv, dtype,
                                         chunks=asked)
        assert 1 <= got <= asked and n % got == 0
    assert schedule.delta_rule_chunks(kernel, bh, t, n, dk, dv, dtype,
                                      chunks=10 ** 6) == n


@pytest.mark.parametrize("dk,dv,chunk,ok", [
    (128, 128, 64, True), (256, 128, 32, True), (128, 384, 16, True),
    (64, 128, 64, False), (128, 8, 64, False), (192, 128, 64, False),
    (128, 128, 40, False), (128, 128, 8, False), (0, 128, 64, False)])
def test_delta_rule_shape_gate(dk, dv, chunk, ok):
    """Heads on the lane grid, a chunk's rows on a 16-bit operand's
    sublane grid: what ``gated_delta_rule`` asks before the kernels."""
    assert schedule.delta_rule_shape_supported(dk, dv, chunk) is ok


@pytest.mark.parametrize("kernel", ["delta_rule_fwd", "delta_rule_bwd"])
def test_delta_rule_workload_and_table_entry_steer_the_kernels(
        kernel, tmp_path, monkeypatch):
    """``delta_rule_workload`` times one kernel alone at its shape and
    dtype over the chunks a grid step may take, and the entry it writes
    is the one the builder's lookup hits."""
    import jax.numpy as jnp

    wl = search.delta_rule_workload(kernel, b=1, t=256, hk=1, hv=2,
                                    interpret=True, dtype="bfloat16")
    assert wl.kernel == kernel and wl.dtype == "bfloat16"
    assert wl.shape_key == "bh2-t256-dk128-dv128"
    # four chunks: a step takes all, two or one of them
    assert wl.candidates() == [{"chunks": 4}, {"chunks": 2}, {"chunks": 1}]
    assert wl.reference() == {"chunks": min(
        4, schedule.DEFAULT_SCHEDULES[kernel]["chunks"])}
    fn, args = wl.build(wl.reference())
    if kernel == "delta_rule_fwd":
        assert [a.dtype for a in args] == [jnp.bfloat16] * 3 \
            + [jnp.float32] * 2
        assert fn(*args).shape == (1, 256, 2, 128)
    else:
        assert [a.shape for a in fn(*args)] == [
            (1, 256, 1, 128)] * 2 + [(1, 256, 2, 128)] + [(1, 256, 2)] * 2
    quick = search.delta_rule_workload(kernel, b=1, t=256, hk=1, hv=2,
                                       interpret=True, quick=True)
    tbl = str(tmp_path / "t.json")
    res = search.run_search(quick, tbl, rounds=1, iters=1)
    assert res["key"] == f"{kernel}|interpret|float32|bh2-t256-dk128-dv128"
    assert res["rejected"] == 0 and res["candidates"] == 2
    won = schedule.load_single_table(tbl)[res["key"]]["schedule"]
    assert set(won) == {"chunks"}
    assert schedule.validate_table(json.load(open(tbl))) == []
    monkeypatch.setenv("MXNET_TPU_SCHEDULE_TABLE", tbl)
    tune.reset_stats()
    assert schedule.delta_rule_chunks(kernel, 2, 256, 4, 128, 128,
                                      "float32", interpret=True) \
        == won["chunks"]
    assert tune.stats()["autotune_table_hits"] == 1


def test_autotune_production_sweep_names_the_delta_rule_cell():
    """``tools/autotune.py`` outside the demo sweeps both kernels at the
    Qwen3-Next cell's shape in bfloat16 (built lazily: nothing runs)."""
    spec = importlib.util.spec_from_file_location(
        "autotune_tool", os.path.join(ROOT, "tools", "autotune.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    found = {wl.label: wl for wl in tool.build_workloads(
        quick=False, interpret=True) if wl.kernel.startswith("delta_rule")}
    assert sorted(found) == ["qwen3next_delta_rule_bwd",
                             "qwen3next_delta_rule_fwd"]
    for wl in found.values():
        assert wl.shape_key == "bh32-t8192-dk128-dv128"
        assert wl.dtype == "bfloat16"
        assert [c["chunks"] for c in wl.candidates()] == [16, 8, 4, 2, 1]
    assert not any(wl.kernel.startswith("delta_rule")
                   for wl in tool.build_workloads(quick=True,
                                                  interpret=True))


def test_flash_fwd_workload_is_keyed_by_its_dtype(tmp_path):
    """The workload builds inputs of the dtype it is given and keys the
    entry by it; bf16 candidates pass the gate at bf16's rounding."""
    import jax.numpy as jnp

    wl = search.flash_fwd_workload(b=1, h=2, t=128, d=16, interpret=True,
                                   quick=True, dtype="bfloat16")
    assert wl.dtype == "bfloat16"
    fn, args = wl.build(wl.reference())
    assert all(a.dtype == jnp.bfloat16 for a in args)
    tbl = str(tmp_path / "t.json")
    res = search.run_search(wl, tbl, rounds=1, iters=1)
    assert res["key"] == "flash_fwd|interpret|bfloat16|bh2-t128-d16"
    assert res["rejected"] == 0 and res["candidates"] == 4
    assert res["key"] in schedule.load_single_table(tbl)
    # the production sweep leaves the narrow tiles out
    wide = search.flash_fwd_workload(b=1, h=1, t=1024, d=16,
                                     interpret=True, min_block=128)
    assert len(wide.candidates()) == 16
    assert min(c["block_k"] for c in wide.candidates()) == 128


def test_outputs_match_half_precision_bar():
    import jax.numpy as jnp

    ref = jnp.asarray([1.0, -0.5, 0.25], jnp.bfloat16)
    ulp = jnp.asarray([1.0078125, -0.5, 0.25], jnp.bfloat16)
    assert measure.outputs_match(ref, ulp)[0]
    assert not measure.outputs_match(
        ref, jnp.asarray([1.1, -0.5, 0.25], jnp.bfloat16))[0]
    # float32 outputs keep the tight bar
    assert not measure.outputs_match(
        ref.astype(jnp.float32), ulp.astype(jnp.float32))[0]


# ----------------------------------------------- candidate numerics parity

def _qkv(b, h, t, d, seed=0):
    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    return [jnp.asarray(rs.randn(b, h, t, d).astype(np.float32) * 0.3)
            for _ in range(3)]


@pytest.mark.parametrize("causal", [False, True])
def test_flash_identical_across_schedules(causal):
    """THE tuner safety property: fwd output and all three grads agree
    across legal schedule candidates (within f32 block-reorder
    tolerance), so a table change can never change results."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_kernels import (flash_attention,
                                              flash_attention_with_grad)

    q, k, v = _qkv(1, 2, 256, 16, seed=3)
    # the backward's sequence lies along lanes: its tiles are multiples
    # of 128 (or T), the forward's of 8
    candidates = [(256, 256), (128, 256), (256, 128), (128, 128),
                  (64, 32)]

    ref_out = None
    ref_g = None
    for bq, bk in candidates:
        out = flash_attention(q, k, v, causal=causal, interpret=True,
                              block_q=bq, block_k=bk)

        def loss(q_, k_, v_, bq=bq, bk=bk):
            o = flash_attention_with_grad(
                q_, k_, v_, causal=causal, interpret=True,
                block_q=bq, block_k=bk, bwd_block_k=bk)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        if ref_out is None:
            ref_out, ref_g = out, g
            continue
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"fwd {bq}x{bk}")
        for a, b, name in zip(g, ref_g, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"grad {name} {bq}x{bk}")


def test_flash_bwd_nondivisible_block_pads_tail():
    """Regression (ISSUE 15 satellite): the scan backward once computed
    n_kb = t // block_k and silently DROP the tail for non-dividing
    blocks. The kernel that replaced it legalizes ``bwd_block_k``: odd T
    with a forced small block (one tile of T) must match dense autodiff
    exactly like the dividing case."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_kernels import flash_attention_with_grad

    t, d = 33, 8
    q, k, v = _qkv(1, 1, t, d, seed=5)

    def loss_flash(q_, k_, v_, bk=None):
        out = flash_attention_with_grad(q_, k_, v_, causal=True,
                                        interpret=True, bwd_block_k=bk)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def loss_dense(q_, k_, v_):
        s = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) / np.sqrt(d)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
        w = jax.nn.softmax(s, -1)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", w, v_) ** 2)

    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for bk in (8, 4, t):  # 33 % 8 = 1, 33 % 4 = 1: neither divides T
        gf = jax.grad(lambda a, b, c: loss_flash(a, b, c, bk=bk),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4,
                                       err_msg=f"grad {name} bk={bk}")


def test_int8_operand_width_exactly_equal():
    """The int8 operand-width axis is EXACT by construction (same
    integer arithmetic, different backend kernel selection)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.quantization import _s8_conv, _s8_matmul

    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randint(-127, 128, (4, 32)).astype(np.int8))
    w = jnp.asarray(rs.randint(-127, 128, (16, 32)).astype(np.int8))
    a = np.asarray(_s8_matmul(x, w, operand_width="int8"))
    b = np.asarray(_s8_matmul(x, w, operand_width="int32"))
    assert a.dtype == b.dtype == np.int32
    assert np.array_equal(a, b)

    xc = jnp.asarray(rs.randint(-127, 128, (2, 8, 6, 6)).astype(np.int8))
    wc = jnp.asarray(rs.randint(-127, 128, (4, 8, 3, 3)).astype(np.int8))
    dn = jax.lax.conv_dimension_numbers(xc.shape, wc.shape,
                                        ("NCHW", "OIHW", "NCHW"))
    ca = np.asarray(_s8_conv(xc, wc, (1, 1), ((1, 1), (1, 1)), (1, 1),
                             dn, 1, operand_width="int8"))
    cb = np.asarray(_s8_conv(xc, wc, (1, 1), ((1, 1), (1, 1)), (1, 1),
                             dn, 1, operand_width="int32"))
    assert np.array_equal(ca, cb)


# --------------------------------------------------------- table semantics

def test_table_roundtrip_and_validation(tmp_path):
    tbl = str(tmp_path / "table.json")
    key = schedule.put_entry(tbl, "flash_fwd", "bh2-t256-d32", "float32",
                             "interpret", {"block_q": 64, "block_k": 128},
                             margin_pct=12.5)
    assert key == "flash_fwd|interpret|float32|bh2-t256-d32"
    data = json.load(open(tbl))
    assert schedule.validate_table(data) == []
    assert data["schema_version"] == schedule.SCHEMA_VERSION
    assert data["entries"][key]["schedule"] == {"block_q": 64,
                                                "block_k": 128}

    # corrupt variants each name a problem
    assert schedule.validate_table([]) != []
    assert any("schema_version" in p for p in schedule.validate_table(
        {"schema_version": 99, "entries": {}}))
    bad = {"schema_version": 1, "entries": {"nokey": {"schedule": {}}}}
    assert any("kernel|backend|dtype|shape" in p
               for p in schedule.validate_table(bad))
    bad = {"schema_version": 1, "entries": {
        "mystery|cpu|int8|s": {"schedule": {"x": 1}}}}
    assert any("unknown kernel" in p for p in schedule.validate_table(bad))
    bad = {"schema_version": 1, "entries": {
        "flash_fwd|cpu|float32|s": {"schedule": {"warp": 4}}}}
    assert any("unknown schedule axis" in p
               for p in schedule.validate_table(bad))
    bad = {"schema_version": 1, "entries": {
        "int8_fc|cpu|int8|s": {"schedule": {"operand_width": "int7"}}}}
    assert any("candidate set" in p for p in schedule.validate_table(bad))


def test_table_feeds_kernel_builders(tmp_path, monkeypatch):
    """A per-host table entry steers the flash builder (counted as a
    table hit) and the kernel still matches the default schedule."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_kernels import flash_attention

    tbl = str(tmp_path / "host.json")
    schedule.put_entry(tbl, "flash_fwd", "bh2-t128-d16", "float32",
                       "interpret", {"block_q": 32, "block_k": 64})
    monkeypatch.setenv("MXNET_TPU_SCHEDULE_TABLE", tbl)
    tune.reset_stats()
    assert schedule.flash_fwd_blocks(2, 128, 16, "float32",
                                     interpret=True) == (32, 64)
    assert tune.stats()["autotune_table_hits"] == 1

    q, k, v = _qkv(1, 2, 128, 16, seed=1)
    tuned = flash_attention(q, k, v, causal=True, interpret=True)
    monkeypatch.delenv("MXNET_TPU_SCHEDULE_TABLE")
    default = flash_attention(q, k, v, causal=True, interpret=True,
                              block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(tuned), np.asarray(default),
                               rtol=2e-5, atol=2e-5)


def test_int8_table_entries_reach_the_kernels(tmp_path, monkeypatch):
    """Closure between the search workloads and the registered int8 ops:
    an entry persisted under a WORKLOAD's shape key must be the entry
    the KERNEL's trace-time lookup hits (review regression: the conv
    sides once formatted the same shape differently, so tuned conv
    wins were silently dead weight in the table)."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.quantization import (_quantized_conv,
                                            _quantized_fully_connected,
                                            _requantize)

    backend = schedule.resolve_backend(False)
    tbl = str(tmp_path / "host.json")
    fc_wl = search.int8_fc_workload(m=4, k=16, n=8)
    conv_wl = search.int8_conv_workload(n=2, c=4, hw=6, o=8)
    rq_wl = search.int8_requant_workload(rows=4, cols=8)
    for wl, sched in ((fc_wl, {"operand_width": "int32"}),
                      (conv_wl, {"operand_width": "int32"}),
                      (rq_wl, {"path": "fused_scale"})):
        schedule.put_entry(tbl, wl.kernel, wl.shape_key, "int8",
                           backend, sched)
    monkeypatch.setenv("MXNET_TPU_SCHEDULE_TABLE", tbl)

    rs = np.random.RandomState(1)
    lo = jnp.asarray(-1.0, jnp.float32)
    hi = jnp.asarray(1.0, jnp.float32)

    tune.reset_stats()
    x = jnp.asarray(rs.randint(-127, 128, (4, 16)).astype(np.int8))
    w = jnp.asarray(rs.randint(-127, 128, (8, 16)).astype(np.int8))
    _quantized_fully_connected(x, w, None, lo, hi, lo, hi, no_bias=True)
    assert tune.stats()["autotune_table_hits"] == 1

    tune.reset_stats()
    xc = jnp.asarray(rs.randint(-127, 128, (2, 4, 6, 6)).astype(np.int8))
    wc = jnp.asarray(rs.randint(-127, 128, (8, 4, 3, 3)).astype(np.int8))
    _quantized_conv(xc, wc, None, lo, hi, lo, hi, stride=(1, 1),
                    pad=(1, 1), no_bias=True)
    assert tune.stats()["autotune_table_hits"] == 1

    tune.reset_stats()
    acc = jnp.asarray(
        rs.randint(-2 ** 28, 2 ** 28, (4, 8)).astype(np.int32))
    _requantize(acc, lo, hi, min_calib_range=-0.9, max_calib_range=0.9)
    assert tune.stats()["autotune_table_hits"] == 1


def test_autotune_kill_switch(tmp_path, monkeypatch):
    tbl = str(tmp_path / "host.json")
    schedule.put_entry(tbl, "flash_fwd", "bh2-t128-d16", "float32",
                       "interpret", {"block_q": 32, "block_k": 64})
    monkeypatch.setenv("MXNET_TPU_SCHEDULE_TABLE", tbl)
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "0")
    # table ignored -> legalized defaults; and the AOT token collapses
    # to '' (default programs share cache identity with no-table hosts)
    assert schedule.flash_fwd_blocks(2, 128, 16, "float32",
                                     interpret=True) == (128, 128)
    assert schedule.fingerprint_token() == ""
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "1")
    assert schedule.fingerprint_token() != ""


def test_counters_reach_profiler():
    from mxnet_tpu import profiler

    s = profiler.dispatch_stats()
    for k in tune._STATS:
        assert k in s, k


# ------------------------------------------------------------- the search

def _toy_workload(tmp_ignored, bad_candidate=False):
    """Synthetic workload driving the gate logic: candidate 'b' returns
    WRONG outputs and must be rejected; 'c' is valid and faster-ish."""
    import jax
    import jax.numpy as jnp

    x = jnp.arange(64, dtype=jnp.float32)

    def build(sched):
        mode = sched["operand_width"]
        if mode == "int8":          # reference
            fn = jax.jit(lambda x: (x * 2.0).sum())
        elif mode == "int32":       # equal value, different arrangement
            fn = jax.jit(lambda x: (x + x).sum())
        return fn, (x,)

    def build_bad(sched):
        if sched["operand_width"] == "int32":
            return jax.jit(lambda x: (x * 3.0).sum()), (x,)
        return build(sched)

    return search.Workload(
        "int8_fc", "toy", "float32", "test",
        build_bad if bad_candidate else build,
        [{"operand_width": "int8"}, {"operand_width": "int32"}])


def test_search_rejects_wrong_candidate(tmp_path):
    from mxnet_tpu.observability import flight

    tbl = str(tmp_path / "t.json")
    tune.reset_stats()
    mark = flight.last_seq()
    res = search.run_search(_toy_workload(tbl, bad_candidate=True), tbl,
                            rounds=1, iters=2)
    assert res["rejected"] == 1
    assert res["winner"] == {"operand_width": "int8"}  # only the ref
    assert tune.stats()["autotune_rejected"] == 1
    assert tune.stats()["autotune_searches"] == 1
    # one autotune flight event carries winner + margin
    evs = flight.events(kind="autotune", since_seq=mark)
    assert len(evs) == 1
    assert evs[0]["winner"] == {"operand_width": "int8"}
    assert "margin_pct" in evs[0] and evs[0]["rejected"] == 1


def test_search_warm_skip_and_force(tmp_path):
    tbl = str(tmp_path / "t.json")
    res = search.run_search(_toy_workload(tbl), tbl, rounds=1, iters=2)
    assert not res["skipped"] and res["rejected"] == 0
    res2 = search.run_search(_toy_workload(tbl), tbl)
    assert res2["skipped"]
    res3 = search.run_search(_toy_workload(tbl), tbl, rounds=1, iters=2,
                             force=True)
    assert not res3["skipped"]


def test_outputs_match_semantics():
    ok, _ = measure.outputs_match(np.float32([1.0, 2.0]),
                                  np.float32([1.0, 2.0 + 1e-6]))
    assert ok
    ok, _ = measure.outputs_match(np.float32([1.0]), np.float32([1.1]))
    assert not ok
    ok, _ = measure.outputs_match(np.int32([1, 2]), np.int32([1, 3]))
    assert not ok  # integer grids are exact
    ok, _ = measure.outputs_match(np.int32([1]), np.float32([1.0]))
    assert not ok  # dtype is identity


# ------------------------------------------------- AOT fingerprint re-key

def test_schedule_table_rekeys_aot_cache(tmp_path, monkeypatch):
    """Acceptance: a schedule-table change re-keys the AOT fingerprint
    (no stale compile-cache hit); an unchanged table + shapes reuses
    the cached executable bit-for-bit."""
    import jax.numpy as jnp

    monkeypatch.setenv("MXNET_TPU_COMPILE_CACHE", str(tmp_path / "cache"))
    tbl = str(tmp_path / "host.json")

    def f(a, b):
        return (a * b + 1.0).sum()

    args = (jnp.ones((4, 4)), jnp.ones((4, 4)))

    capture.reset_stats()
    ex = capture.aot_compile(f, label="t", fingerprint="fp",
                             example_args=args)
    cold = np.asarray(ex(*args))
    assert capture.stats()["aot_cache_writes"] == 1

    # unchanged world -> warm hit, bit-for-bit
    capture.reset_stats()
    ex2 = capture.aot_compile(f, label="t", fingerprint="fp",
                              example_args=args)
    s = capture.stats()
    assert s["aot_cache_hits"] == 1 and s["aot_cache_misses"] == 0
    assert np.array_equal(cold, np.asarray(ex2(*args)))

    # a schedule table appears -> key changes -> miss + fresh store
    schedule.put_entry(tbl, "flash_fwd", "bh2-t128-d16", "float32",
                       "interpret", {"block_q": 64, "block_k": 64})
    monkeypatch.setenv("MXNET_TPU_SCHEDULE_TABLE", tbl)
    capture.reset_stats()
    capture.aot_compile(f, label="t", fingerprint="fp", example_args=args)
    s = capture.stats()
    assert s["aot_cache_misses"] == 1 and s["aot_cache_hits"] == 0

    # same table content -> warm again
    capture.reset_stats()
    capture.aot_compile(f, label="t", fingerprint="fp", example_args=args)
    assert capture.stats()["aot_cache_hits"] == 1

    # an EDIT to the table -> re-key again
    schedule.put_entry(tbl, "flash_fwd", "bh2-t128-d16", "float32",
                       "interpret", {"block_q": 32, "block_k": 64})
    capture.reset_stats()
    capture.aot_compile(f, label="t", fingerprint="fp", example_args=args)
    s = capture.stats()
    assert s["aot_cache_misses"] == 1 and s["aot_cache_hits"] == 0


def test_ring_fn_cache_keys_on_table_digest(tmp_path, monkeypatch):
    """The in-process jitted ring-attention program re-keys when the
    table changes (the per-hop flash blocks resolve at trace time), and
    the re-traced program agrees numerically — a table edit can change
    the schedule, never the results."""
    import jax.numpy as jnp

    from mxnet_tpu import parallel
    from mxnet_tpu.parallel import ring as ra

    import jax

    tbl = str(tmp_path / "host.json")
    monkeypatch.setenv("MXNET_TPU_SCHEDULE_TABLE", tbl)
    mesh = parallel.create_mesh({"sp": 2}, jax.devices()[:2])
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 1, 128, 16).astype(np.float32) * 0.3)

    info0 = ra._ring_fn.cache_info()
    out1 = ra.ring_attention(q, q, q, mesh=mesh, causal=True,
                             impl="flash", interpret=True)
    # tune the hop shape (t_local = 64) -> digest moves -> fresh program
    schedule.put_entry(tbl, "flash_fwd", "bh1-t64-d16", "float32",
                       "interpret", {"block_q": 32, "block_k": 32})
    out2 = ra.ring_attention(q, q, q, mesh=mesh, causal=True,
                             impl="flash", interpret=True)
    info1 = ra._ring_fn.cache_info()
    assert info1.misses - info0.misses == 2
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=2e-5, atol=2e-5)
    # the kill switch collapses the tag too (review regression: the
    # cached tuned program must not survive MXNET_TPU_AUTOTUNE=0)
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "0")
    out3 = ra.ring_attention(q, q, q, mesh=mesh, causal=True,
                             impl="flash", interpret=True)
    info2 = ra._ring_fn.cache_info()
    assert info2.misses - info1.misses == 1
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out3),
                               rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------- demo contract

def _autotune_main():
    spec = importlib.util.spec_from_file_location(
        "autotune_under_test", os.path.join(ROOT, "tools", "autotune.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_autotune_demo_cold_then_warm(tmp_path, monkeypatch, capsys):
    """Acceptance: --demo runs end-to-end on CPU/interpret (candidate
    generation -> numerics validation -> winner persisted) and a second
    run does ZERO searches because the table is warm."""
    tbl = str(tmp_path / "demo.json")
    monkeypatch.delenv("MXNET_TPU_SCHEDULE_TABLE", raising=False)
    mod = _autotune_main()
    assert mod.main(["--demo", "--table", tbl]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["metric"] == "autotune_searches"
    assert out["value"] == 9 and out["extra"]["errors"] == 0
    data = json.load(open(tbl))
    assert schedule.validate_table(data) == []
    assert len(data["entries"]) == 9
    # the sweep covers flash fwd/bwd, the ring hop and transformer
    # head shapes, paged decode attention, and int8
    kernels = {k.split("|")[0] for k in data["entries"]}
    assert kernels == {"flash_fwd", "flash_bwd", "decode_attn", "int8_fc",
                       "int8_conv", "int8_requant"}
    labels = {r["label"] for r in out["extra"]["results"]}
    assert "ring_hop" in labels

    # warm second run: zero searches, all skipped
    assert mod.main(["--demo", "--table", tbl]) == 0
    out2 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out2["value"] == 0
    assert out2["extra"]["skipped_warm"] == 9


@pytest.mark.slow
def test_autotune_demo_cli_contract(tmp_path):
    """Subprocess contract: one JSON line on stdout, exit 0."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("MXNET_TPU_SCHEDULE_TABLE", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "autotune.py"),
         "--demo", "--table", str(tmp_path / "cli.json")],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["metric"] == "autotune_searches" and out["value"] == 9


def test_validate_baselines_schedule_table_cli(tmp_path):
    """tools/validate_baselines.py --schedule-table audits the table
    offline (no jax import needed for the check itself)."""
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({
        "schema_version": 1,
        "entries": {"mystery|cpu|int8|s": {"schedule": {"x": 1}}}}))
    env = dict(os.environ)
    r = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "tools", "validate_baselines.py"),
         "--schedule-table", str(bad),
         "--report", str(tmp_path / "rep.json")],
        capture_output=True, text=True, env=env, timeout=240)
    assert r.returncode != 0
    rep = json.load(open(tmp_path / "rep.json"))
    [res] = [x for x in rep["results"] if x["name"] == "schedule_table"]
    assert res["status"] == "failed" and res["problems"]

    # the committed table passes
    r2 = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "tools", "validate_baselines.py"),
         "--schedule-table",
         "--report", str(tmp_path / "rep2.json")],
        capture_output=True, text=True, env=env, timeout=240)
    assert r2.returncode == 0, r2.stdout + r2.stderr
