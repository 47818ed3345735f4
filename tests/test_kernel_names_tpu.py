"""Every Pallas kernel carries a ``name=``: lowered for a described TPU
(``v5e:2x2``, nothing attached, nothing run) the custom call and its
``op_name`` hold it, which is what a device trace and
``observability.perf.op_names`` then show. The one file that describes
the topology: the call is made inside a fixture, never at import, and
the compile happens in the test's own process (only one process at a
time may load the TPU's library)."""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here: say why, do not fail
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _lowered(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def test_flash_forward_is_named_in_the_lowered_program(one_chip):
    from mxnet_tpu.ops import pallas_kernels

    qkv = ((4, 2, 256, 64), jnp.bfloat16)
    text = _lowered(
        lambda q, k, v: pallas_kernels.flash_attention(q, k, v, causal=True),
        one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in text
    assert 'kernel_name = "flash_attention_fwd"' in text
    assert "flash_attention_fwd/pallas_call" in text


# (B, H, T, D), dtype: the GPT-2 medium training cell's attention, the
# widest head in float32, sequences off the lane tile, a tile narrower
# than a lane tile by explicit blocks
_FLASH_SHAPES = [((8, 16, 1024, 64), jnp.bfloat16, {}),
                 ((1, 2, 1024, 256), jnp.float32, {}),
                 ((1, 2, 2048, 128), jnp.bfloat16, {}),
                 ((1, 2, 65, 64), jnp.bfloat16, {}),
                 ((1, 2, 96, 32), jnp.float32, {}),
                 ((1, 1, 200, 32), jnp.float32, {}),
                 ((1, 1, 200, 32), jnp.bfloat16,
                  {"block_q": 40, "block_k": 40}),
                 ((1, 2, 256, 64), jnp.bfloat16,
                  {"block_q": 64, "block_k": 128})]


@pytest.mark.parametrize("shape,dtype,blocks", _FLASH_SHAPES)
def test_flash_forward_compiles_at_real_widths(one_chip, shape, dtype,
                                               blocks):
    """What interpret mode cannot show: Mosaic takes the tile program
    (block shapes, the lane-dense lse rows, VMEM) at these widths, with
    traced hop offsets, under the default schedule by shape."""
    from mxnet_tpu.ops import pallas_kernels

    qkv = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    off = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda q, k, v, qo, ko: pallas_kernels.flash_attention(
            q, k, v, causal=True, return_lse=True, q_offset=qo,
            k_offset=ko, **blocks)).lower(qkv, qkv, qkv, off, off).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _grad_through_flash(blocks):
    """q, k, v, traced offsets -> (dq, dk, dv) under the ``attention``
    scope, as ``MultiHeadAttention`` and the ring hop call it."""
    from mxnet_tpu.ops import pallas_kernels

    def loss(q, k, v, qo, ko):
        with jax.named_scope("attention"):
            out, lse = pallas_kernels.flash_attention_with_lse(
                q, k, v, causal=True, q_offset=qo, k_offset=ko,
                bwd_block_k=blocks.get("block_k"), **blocks)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)

    return jax.grad(loss, argnums=(0, 1, 2))


# the forward's shapes under the backward's default tile (its sequence lies
# along lanes: a tile is a multiple of 128 or all of T), explicit tiles of
# its own, a long sequence off the lane grid (padded to 2048), and the two
# training cells' attention: GPT-2 medium (first) and Qwen3-Next's gated
# attention after the K/V repeat
_FLASH_BWD_SHAPES = [(shape, dtype, {}) for shape, dtype, blocks
                     in _FLASH_SHAPES if not blocks] + [
    ((1, 2, 256, 64), jnp.bfloat16, {"block_q": 128, "block_k": 256}),
    ((1, 2, 2000, 64), jnp.bfloat16, {}),
    ((1, 16, 8192, 256), jnp.bfloat16, {})]


@pytest.mark.parametrize("shape,dtype,blocks", _FLASH_BWD_SHAPES)
def test_flash_backward_is_named_and_compiles_at_real_widths(
        one_chip, shape, dtype, blocks):
    """``jax.grad`` through the custom_vjp: the backward is one kernel,
    named, under the backward half of the ``attention`` scope (what
    ``attention_bwd_ms_per_step`` joins on), and Mosaic takes its tile
    program at these widths with traced hop offsets and a non-zero
    ``dlse``."""
    qkv = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    off = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    lowered = jax.jit(_grad_through_flash(blocks)).lower(
        qkv, qkv, qkv, off, off)
    text = lowered.as_text(debug_info=True)
    assert 'kernel_name = "flash_attention_bwd"' in text
    named = [line for line in text.splitlines()
             if "flash_attention_bwd/pallas_call" in line]
    assert named and all("transpose(jvp(attention))" in line
                         for line in named), named[:2]
    compiled = lowered.compile().as_text()
    assert compiled.count("tpu_custom_call") >= 2
    assert "while" not in compiled      # no scan is left in the backward


# ((B, H, T, D), dtype, window, the offsets traced): a window layer of
# Trinity-Mini's cell (static offsets: the grids count their steps
# exactly, 5 of 16); the same as a ring hop would call it; a window off
# the tile grid; a short sequence off the lane grid
_WINDOW_SHAPES = [((1, 32, 8192, 128), jnp.bfloat16, 2048, False),
                  ((1, 4, 8192, 128), jnp.bfloat16, 2048, True),
                  ((1, 2, 2048, 64), jnp.bfloat16, 700, False),
                  ((1, 2, 200, 32), jnp.float32, 77, True)]


@pytest.mark.parametrize("shape,dtype,window,traced", _WINDOW_SHAPES)
def test_window_kernels_are_named_and_compile_at_real_widths(
        one_chip, shape, dtype, window, traced):
    """Under a window both kernels keep their names (one kernel each,
    window or not), sit under the ``window_attention`` scope where the
    block opens it, and Mosaic takes the shortened grids and the
    clamped index maps at these widths."""
    from mxnet_tpu.ops import pallas_kernels

    def loss(q, k, v, qo, ko):
        with jax.named_scope("attention"), \
                jax.named_scope("window_attention"):
            if traced:
                out, _ = pallas_kernels.flash_attention_with_lse(
                    q, k, v, causal=True, q_offset=qo, k_offset=ko,
                    window=window)
            else:
                out = pallas_kernels.flash_attention_with_grad(
                    q, k, v, causal=True, window=window)
        return jnp.sum(out.astype(jnp.float32))

    qkv = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    off = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv, off, off)
    text = lowered.as_text(debug_info=True)
    for kernel in ("flash_attention_fwd", "flash_attention_bwd"):
        assert f'kernel_name = "{kernel}"' in text
        named = [line for line in text.splitlines()
                 if f"{kernel}/pallas_call" in line]
        assert named and all(f"window_attention/{kernel}" in line
                             for line in named), named[:2]
    compiled = lowered.compile().as_text()
    assert compiled.count("tpu_custom_call") >= 2
    assert "while" not in compiled


def _through_delta_rule(*args):
    """The gated delta rule under the ``linear_attention`` scope, as
    ``GatedDeltaNet`` calls it, straight through the kernels (the
    dispatch of ``gated_delta_rule`` asks jax's default backend, which
    is the CPU here)."""
    from mxnet_tpu.ops import delta_rule_kernels

    with jax.named_scope("linear_attention"):
        return delta_rule_kernels.gated_delta_rule_kernels(*args)


# (B, T, key heads, value heads, Dk, Dv), dtype: the Qwen3-Next cell's
# linear attention, a sequence off the chunk grid with heads of two
# sizes in float32, one key head a value head
_DELTA_RULE_SHAPES = [((1, 8192, 16, 32, 128, 128), jnp.bfloat16),
                      ((2, 200, 2, 4, 128, 256), jnp.float32),
                      ((1, 520, 2, 2, 256, 128), jnp.bfloat16)]


@pytest.mark.parametrize("shape,dtype", _DELTA_RULE_SHAPES)
def test_delta_rule_kernels_are_named_and_compile_at_real_widths(
        one_chip, shape, dtype):
    """Forward alone: one kernel, ``gated_delta_rule_fwd``. Under
    ``jax.grad``: the forward that saves the entering states under the
    forward half of the ``linear_attention`` scope and
    ``gated_delta_rule_bwd`` under the backward half (what
    ``linear_attention_*_ms_per_step`` join on), Mosaic takes both tile
    programs at these widths, and neither the scan nor the triangular
    solve of the ``jax.numpy`` form is left."""
    b, t, hk, hv, dk, dv = shape
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((b, t, hk, dk), dtype), ((b, t, hk, dk), dtype),
        ((b, t, hv, dv), dtype), ((b, t, hv), jnp.float32),
        ((b, t, hv), jnp.float32))]
    forward = jax.jit(_through_delta_rule).lower(*args)
    text = forward.as_text(debug_info=True)
    assert 'kernel_name = "gated_delta_rule_fwd"' in text
    assert "linear_attention/gated_delta_rule_fwd/pallas_call" in text
    compiled = forward.compile().as_text()
    assert compiled.count("tpu_custom_call") >= 1
    assert "while" not in compiled and "triangular" not in compiled

    lowered = jax.jit(jax.grad(
        lambda *a: jnp.sum(_through_delta_rule(*a).astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4))).lower(*args)
    text = lowered.as_text(debug_info=True)
    for kernel, half in (("gated_delta_rule_fwd", "jvp(linear_attention)"),
                         ("gated_delta_rule_bwd",
                          "transpose(jvp(linear_attention))")):
        assert f'kernel_name = "{kernel}"' in text
        named = [line for line in text.splitlines()
                 if f"{kernel}/pallas_call" in line]
        assert named and all(f"{half}/{kernel}" in line for line in named), \
            named[:2]
    compiled = lowered.compile().as_text()
    assert compiled.count("tpu_custom_call") >= 2
    assert "while" not in compiled and "triangular" not in compiled


# ((B, T, W), the parts, taps), dtype: the projection's output of a
# Qwen3-Next linear layer in the cell; two sequences that end inside
# their last row tile, unequal parts, float32, two taps
_CONV_SILU_SHAPES = [(((1, 8192, 12288), (2048, 2048, 4096), 4),
                      jnp.bfloat16),
                     (((2, 600, 640), (128, 256, 128), 2), jnp.float32)]


@pytest.mark.parametrize("shape,dtype", _CONV_SILU_SHAPES)
def test_conv_silu_kernels_are_named_and_compile_at_real_widths(
        one_chip, shape, dtype):
    """Forward alone: a ``causal_conv_silu_fwd`` a part and no slice or
    pad of the convolved columns. Under ``jax.grad``: the forward under
    the forward half of the ``linear_attention`` scope and a
    ``causal_conv_silu_bwd`` a part under the backward half (what
    ``linear_attention_*_ms_per_step`` join on), the parts' input
    gradients written into one array that no copy or concatenation
    touches, and Mosaic takes both tile programs at these widths."""
    from mxnet_tpu.ops import conv_silu_kernels

    (b, t, width), parts, taps = shape

    def over_the_sequence(compiled, *ops):
        """Lines of the compiled program that run one of ``ops`` on a
        (B, T, ..) tensor (the weight's are a few KB)."""
        return [line for line in compiled.splitlines()
                if any(f" {op}(" in line for op in ops)
                and f"[{b},{t}," in line]

    def through(x, w):
        with jax.named_scope("linear_attention"):
            return conv_silu_kernels.causal_conv_silu_kernels(x, w, parts)

    args = [jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
            for s in ((b, t, width), (sum(parts), taps))]
    forward = jax.jit(lambda x, w: through(x, w)[:-1]).lower(*args)
    text = forward.as_text(debug_info=True)
    assert 'kernel_name = "causal_conv_silu_fwd"' in text
    assert "linear_attention/causal_conv_silu_fwd/pallas_call" in text
    compiled = forward.compile().as_text()
    assert compiled.count("custom_call_target=\"tpu_custom_call\"") == \
        len(parts)
    assert not over_the_sequence(compiled, "slice", "pad")

    lowered = jax.jit(jax.grad(
        lambda x, w: sum(jnp.sum(o.astype(jnp.float32) ** 2)
                         for o in through(x, w)), argnums=(0, 1))).lower(
                             *args)
    text = lowered.as_text(debug_info=True)
    for kernel, half in (("causal_conv_silu_fwd", "jvp(linear_attention)"),
                         ("causal_conv_silu_bwd",
                          "transpose(jvp(linear_attention))")):
        assert f'kernel_name = "{kernel}"' in text
        named = [line for line in text.splitlines()
                 if f"{kernel}/pallas_call" in line]
        assert named and all(f"{half}/{kernel}" in line for line in named), \
            named[:2]
    compiled = lowered.compile().as_text()
    assert compiled.count("custom_call_target=\"tpu_custom_call\"") == \
        2 * len(parts)
    assert not over_the_sequence(compiled, "copy", "concatenate", "pad")


# (rows, d, inner wide, held experts): an expert's two products in the
# three expert cells (inner wide: the matrix going up, both halves of a
# gated expert), a block of 8192 sorted rows
_GROUPED_SHAPES = [(8192, 2688, 1856, 8), (8192, 2048, 2048, 8),
                   (8192, 2048, 1024, 16)]


@pytest.mark.parametrize("rows,d,wide,held", _GROUPED_SHAPES)
def test_grouped_matmul_kernels_are_named_and_compile_at_real_widths(
        one_chip, rows, d, wide, held):
    """Under ``jax.grad`` of an expert's products (up, a squared ReLU,
    down) through the kernels under the ``moe_experts`` scope:
    ``grouped_matmul`` under the forward half (both products),
    ``grouped_matmul`` again for the input gradients and
    ``grouped_matmul_t`` for the weight gradients under the backward
    half (what ``moe_*_ms_per_step`` join on), Mosaic takes the tile
    programs at these widths (off the lane grid in Nemotron-H's), and
    no ``ragged_dot`` is left."""
    from mxnet_tpu.ops import grouped_matmul_kernels

    inner = wide if held == 8 and d != wide else wide // 2

    def loss(x, up, down, sizes):
        with jax.named_scope("moe_experts"):
            h = grouped_matmul_kernels.grouped_matmul_kernels(x, up, sizes)
            a = jnp.square(jax.nn.relu(h[:, :inner]))
            y = grouped_matmul_kernels.grouped_matmul_kernels(a, down, sizes)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((rows, d), jnp.bfloat16), ((held, d, wide), jnp.bfloat16),
        ((held, inner, d), jnp.bfloat16), ((held,), jnp.int32))]
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args)
    text = lowered.as_text(debug_info=True)
    for kernel in ("grouped_matmul", "grouped_matmul_t"):
        assert f'kernel_name = "{kernel}"' in text
    # each kernel is traced once a shape and tile and bound again at
    # every call site: the names are read where XLA leaves them, the
    # compiled program's op_name of each custom call
    compiled = lowered.compile().as_text()
    names = [line.split('op_name="')[1].split('"')[0]
             for line in compiled.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(names) == 6 and all(
        "jvp(moe_experts)" in name for name in names), names
    backward = "transpose(jvp(moe_experts))"
    halves = sorted((name.split("/")[-2], backward in name)
                    for name in names)
    assert halves == [("grouped_matmul", False)] * 2 + [
        ("grouped_matmul", True)] * 2 + [("grouped_matmul_t", True)] * 2, \
        names
    assert "ragged" not in compiled


def test_no_pallas_call_in_the_package_is_left_unnamed():
    """A kernel added later is named the same way (docs/observability.md,
    "The program's own names")."""
    import ast
    import os

    import mxnet_tpu

    root = os.path.dirname(mxnet_tpu.__file__)
    unnamed = []
    for folder, _, files in os.walk(root):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(folder, fname)
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Call) and isinstance(
                        node.func, ast.Attribute) \
                        and node.func.attr == "pallas_call" \
                        and "name" not in {k.arg for k in node.keywords}:
                    unnamed.append(f"{os.path.relpath(path, root)}:"
                                   f"{node.lineno}")
    assert not unnamed, unnamed
