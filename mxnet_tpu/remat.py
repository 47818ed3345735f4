"""Activation rematerialization (gradient mirroring).

The TPU-native counterpart of the reference's backward mirroring
(``MXNET_BACKWARD_DO_MIRROR`` read at src/executor/graph_executor.cc:357;
mirror pass src/nnvm/gradient.cc:107-148): instead of a graph pass marking
cheap nodes for recompute, the traced forward is wrapped in
``jax.checkpoint`` and the backward recomputes the activations that were
not kept — FLOPs for HBM, the trade a step takes when its activations
do not fit the chip beside the training state (the two expert cells of
PERF.md section 4 compile at 8192 tokens only this way).

What a rematerialized region keeps is decided in one place,
:func:`resolve_policy`, and its default keeps one kind of value: what a
hand-written kernel hands to its own backward kernel. The flash
attention forward and the gated delta rule's forward do O(T^2) or
sequential work for an O(T) result, so running them a second time in
the backward costs milliseconds where keeping ``out`` / ``lse`` (and
the delta rule's chunk states and inverses) costs a few tens to a few
hundred MB. Their ``custom_vjp`` forward rules wrap those values in
``jax.ad_checkpoint.checkpoint_name(..., KERNEL_RESIDUAL)``; outside
``jax.checkpoint`` the name lowers to nothing. Everything else in the
region (projections, convolutions, gates, norms) is recomputed as before.

Entry points:
- ``ShardedTrainer(..., remat=...)`` — whole-forward policy remat.
- ``gluon.contrib.Remat(block)`` — segment-level remat around any block.
- env ``MXNET_BACKWARD_DO_MIRROR=1`` — reference-parity switch; picked up
  by both paths and by ``Executor`` bind.
"""
from __future__ import annotations

__all__ = ["KERNEL_RESIDUAL", "kernel_residuals", "resolve_policy",
           "policy_name", "mirror_enabled"]

# the one checkpoint name of this package: a kernel's forward result and
# statistics that its backward kernel reads (ops/pallas_kernels.py,
# ops/delta_rule_kernels.py)
KERNEL_RESIDUAL = "kernel_residual"


def kernel_residuals(*values):
    """``values`` named ``KERNEL_RESIDUAL``, for a kernel's ``custom_vjp``
    forward rule: the named value has to be both what the rule returns
    as its result and what it hands the backward rule, or the
    recomputation still needs the kernel for the other."""
    from jax.ad_checkpoint import checkpoint_name

    return tuple(checkpoint_name(v, KERNEL_RESIDUAL) for v in values)


def mirror_enabled():
    """True when the reference's mirroring env flag is set."""
    from .util import getenv

    v = getenv("MXNET_BACKWARD_DO_MIRROR")
    return v not in (None, "", "0", "false", "False")


def resolve_policy(spec):
    """Map a user remat spec to a jax.checkpoint policy.

    - ``True``/``None`` -> keep what the kernels named
      ``KERNEL_RESIDUAL`` and recompute everything else (a region with
      no kernel in it keeps nothing but its input: the reference
      mirror's spirit)
    - a string -> attribute of ``jax.checkpoint_policies``
      (``'nothing_saveable'`` runs the kernels again in the backward too;
      ``'dots_with_no_batch_dims_saveable'`` for transformer stacks,
      keeping matmul outputs and recomputing elementwise chains)
    - a callable -> used as the policy directly
    """
    import jax

    if spec is None or spec is True:
        return jax.checkpoint_policies.save_only_these_names(KERNEL_RESIDUAL)
    if isinstance(spec, str):
        try:
            return getattr(jax.checkpoint_policies, spec)
        except AttributeError:
            raise ValueError(
                f"unknown remat policy '{spec}'; see jax.checkpoint_policies")
    if callable(spec):
        return spec
    raise TypeError(f"remat spec must be bool/str/callable, got {type(spec)}")


def policy_name(spec):
    """What a trace calls the policy :func:`resolve_policy` gives for
    ``spec`` (the ``remat.trace`` span's ``policy`` attribute)."""
    if spec is None or spec is True:
        return f"save_only_these_names({KERNEL_RESIDUAL})"
    if isinstance(spec, str):
        return spec
    return getattr(spec, "__name__", type(spec).__name__)
