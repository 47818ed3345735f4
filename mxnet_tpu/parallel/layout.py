"""SpecLayout — per-parameter PartitionSpec assignment for transformer
blocks on a named dp×fsdp×tp mesh (docs/parallel.md).

The canonical data/fsdp/tensor layout (scaling-book style, SNIPPETS.md
[3]) adapted to gluon's Dense weight convention ``W: (units_out,
in_units)`` with ``y = x @ W.T``:

- QKV / FFN-up projections are COLUMN-parallel: the output features
  split over ``tp`` (each tp shard computes a head/neuron slice, no
  collective needed on the way in), so gluon's (out, in) weight is
  ``P(tp, fsdp)``.
- attention-output / FFN-down projections are ROW-parallel: the input
  features arrive tp-sharded from the column-parallel producer, so the
  contraction dim splits over ``tp`` and XLA inserts the one
  all-reduce per block: ``P(fsdp, tp)``.
- embedding and LM-head tables shard their vocab rows over the whole
  non-data parameter surface ``(fsdp, tp)`` — the biggest tables get
  the most shards.
- the stacked matrices of an expert layer, (experts, in, out), split
  their leading axis over ``ep`` (parameters only: on a mesh with an
  ``ep`` axis the expert layer's exchange is still to come; on one
  without, the axis drops and they replicate).
- everything else (norm scales, small biases, a router) stays
  replicated; the column-parallel biases follow their weight's output
  split (``tp``).

ShardedTrainer consumes this as ``param_rules`` — an ordered
``(regex, PartitionSpec)`` list, first match wins, unmatched params
replicate — so SpecLayout is pure data: no model surgery, and the same
rules drive the captured and uncaptured step identically.
"""
from __future__ import annotations

__all__ = ["SpecLayout"]


class SpecLayout:
    """Assigns PartitionSpecs to gluon transformer parameters.

    ``data_axis``/``fsdp_axis``/``tp_axis`` name the mesh axes; pass
    None (or use :meth:`for_mesh`) to drop an axis the mesh doesn't
    have — the layout then degrades gracefully (dp-only meshes get pure
    data parallelism with replicated params, dp×tp meshes get tensor
    parallelism without parameter sharding, and so on).
    """

    def __init__(self, data_axis="dp", fsdp_axis="fsdp", tp_axis="tp",
                 ep_axis="ep"):
        self.data_axis = data_axis
        self.fsdp_axis = fsdp_axis
        self.tp_axis = tp_axis
        self.ep_axis = ep_axis

    @classmethod
    def for_mesh(cls, mesh, data_axis="dp", fsdp_axis="fsdp",
                 tp_axis="tp", ep_axis="ep"):
        """A SpecLayout with every axis the mesh lacks dropped to None."""
        names = set(mesh.axis_names)
        return cls(data_axis=data_axis if data_axis in names else None,
                   fsdp_axis=fsdp_axis if fsdp_axis in names else None,
                   tp_axis=tp_axis if tp_axis in names else None,
                   ep_axis=ep_axis if ep_axis in names else None)

    # ----------------------------------------------------------- specs
    def _spec(self, *dims):
        """Build a PartitionSpec, collapsing dropped axes to None."""
        from jax.sharding import PartitionSpec as P

        out = []
        for d in dims:
            if isinstance(d, tuple):
                kept = tuple(a for a in d if a is not None)
                out.append(kept if kept else None)
            else:
                out.append(d)
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def qkv_projection(self):
        """(3·units, units) column-parallel: heads split over tp."""
        return self._spec(self.tp_axis, self.fsdp_axis)

    def attn_output(self):
        """(units, units) row-parallel: contraction dim over tp."""
        return self._spec(self.fsdp_axis, self.tp_axis)

    def ffn_up(self):
        """(4·units, units) column-parallel."""
        return self._spec(self.tp_axis, self.fsdp_axis)

    def ffn_down(self):
        """(units, 4·units) row-parallel."""
        return self._spec(self.fsdp_axis, self.tp_axis)

    def embedding(self):
        """(vocab, units) vocab rows over the full parameter surface."""
        return self._spec((self.fsdp_axis, self.tp_axis), None)

    def lm_head(self):
        """(vocab, units) — same table shape as the embedding."""
        return self._spec((self.fsdp_axis, self.tp_axis), None)

    def experts(self):
        """(experts, in, out) stacked expert matrices: experts over ep."""
        return self._spec(self.ep_axis)

    def column_bias(self):
        """Bias of a column-parallel projection follows its out split."""
        return self._spec(self.tp_axis)

    def replicated(self):
        return self._spec()

    # ------------------------------------------------------ rule table
    def param_rules(self):
        """Ordered (regex, PartitionSpec) rules for ShardedTrainer.

        Written against the model_zoo transformer's stable param
        suffixes (gluon prefixes: ``attn_qkv_``/``attn_out_`` inside
        MultiHeadAttention, ``ff1_``/``ff2_`` for the MLP,
        ``embed_``/``head_`` for the tables) and qwen3_next's
        (``attn_q_``/``attn_k_``/``attn_v_``, ``linattn_qkvz_``/
        ``linattn_ba_``/``linattn_out_``, ``moe_experts_*``,
        ``moe_shared_gate_up_``/``moe_shared_down_``) and trinity's
        (``attn_gate_``, ``mlp_gate_up_``/``mlp_down_``) and nemotron_h's
        (``mamba_in_``/``mamba_out_``, ``moe_experts_up_``,
        ``moe_shared_up_``); first match wins
        and anything unmatched — norms, positional table, small biases,
        a router, a depthwise convolution — replicates, which is exactly
        the layout's intent.
        """
        return (
            (r".*attn_qkv_weight$", self.qkv_projection()),
            (r".*attn_qkv_bias$", self.column_bias()),
            (r".*attn_([qkv]|gate)_weight$", self.qkv_projection()),
            (r".*linattn_(qkvz|ba)_weight$", self.qkv_projection()),
            (r".*mamba_in_weight$", self.qkv_projection()),
            (r".*(attn|linattn|mamba)_out_weight$", self.attn_output()),
            (r".*moe_experts_(gate_up|up|down)_weight$", self.experts()),
            (r".*(moe_shared|mlp)_(gate_up|up)_weight$", self.ffn_up()),
            (r".*(moe_shared|mlp)_down_weight$", self.ffn_down()),
            (r".*ff1_weight$", self.ffn_up()),
            (r".*ff1_bias$", self.column_bias()),
            (r".*ff2_weight$", self.ffn_down()),
            (r".*embed_weight$", self.embedding()),
            (r".*head_weight$", self.lm_head()),
        )

    # ------------------------------------------------------ batch side
    def batch_axes(self):
        """Mesh axes the batch dim shards over: dp and (flat-data) fsdp."""
        return tuple(a for a in (self.data_axis, self.fsdp_axis)
                     if a is not None)

    def batch_spec(self):
        """PartitionSpec for (B, ...) batches: dim 0 over dp×fsdp."""
        return self._spec(self.batch_axes())

    def __repr__(self):
        return (f"SpecLayout(data={self.data_axis!r}, "
                f"fsdp={self.fsdp_axis!r}, tp={self.tp_axis!r}, "
                f"ep={self.ep_axis!r})")
