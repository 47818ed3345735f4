"""INT8 model quantization driver.

Capability parity with python/mxnet/contrib/quantization.py
(quantize_model graph pass + calibration) and
src/operator/quantization/calibrate.cc (entropy/KL threshold search).

Two graph modes:
- quantize_mode='fake' — fp32 values rounded through the int8 grid at
  every quantized boundary (accuracy flow; one XLA program).
- quantize_mode='full' — FullyConnected/Convolution replaced by REAL
  int8 kernels (ops/quantization.py quantized_* — int8 operands, int32
  MXU accumulation), quantize/dequantize at the boundaries. Requires
  calibrated ranges (calib_mode 'naive' or 'entropy').

Calibration modes: 'none' (runtime min/max), 'naive' (min/max over a
calibration set), 'entropy' (KL-divergence-optimal clip threshold over
activation histograms — calibrate.cc).

Calibration is a product step (docs/quantization.md): :func:`calibrate`
returns a :class:`CalibrationTable` (per-tensor thresholds + calib mode
+ sample count) that serving hosts ship next to the params file, so a
`Predictor` quantizes WITHOUT calibration data; applying a table to a
model it was not calibrated for raises :class:`CalibrationMismatchError`
instead of silently serving mis-scaled answers. Collectors accumulate
min/max and |activation| histograms ON DEVICE and pull one small result
per monitored tensor per batch (not one full-tensor transfer per
histogram), timed by the ``calib_*`` counters in
``profiler.dispatch_stats()``.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
import time

import numpy as np

from ..base import MXNetError
from ..resilience import faults as _faults

__all__ = ["quantize_model", "quantize_graph", "fold_batch_norm",
           "calibrate", "CalibrationTable", "CalibrationMismatchError",
           "symbol_digest", "stats", "reset_stats"]

# Calibration observability (merged into profiler.dispatch_stats()).
_STATS = {
    "calib_batches": 0,       # calibration batches fed through the graph
    "calib_tensor_syncs": 0,  # device->host pulls (one per monitored
                              # tensor per batch: a scalar pair or a
                              # histogram, never the full activation)
    "calib_ms": 0,            # cumulative wall-clock ms in the collectors
    "calib_tables_saved": 0,  # CalibrationTable.save() calls
    "calib_tables_loaded": 0, # CalibrationTable.load() calls
    "calib_mismatches": 0,    # stale table/model pairs rejected
}


def stats():
    return dict(_STATS)


def reset_stats():
    for k in _STATS:
        _STATS[k] = 0


@contextlib.contextmanager
def _calib_timer():
    t0 = time.perf_counter()
    try:
        yield
    finally:
        _STATS["calib_ms"] += int((time.perf_counter() - t0) * 1e3)


def _calib_bins(num_bins=None):
    if num_bins is not None:
        return int(num_bins)
    v = os.environ.get("MXNET_TPU_INT8_CALIB_BINS", "").strip()
    return int(v) if v else 2048


_QUANTIZABLE = ("FullyConnected", "Convolution")


_FULL_OPS = {"FullyConnected": "_contrib_quantized_fully_connected",
             "Convolution": "_contrib_quantized_conv"}
_FULL_PARAMS = {
    "FullyConnected": ("num_hidden", "no_bias", "flatten"),
    "Convolution": ("kernel", "stride", "dilate", "pad", "num_filter",
                    "num_group", "no_bias", "layout"),
}


def quantize_graph(sym, excluded_sym_names=(), quantized_dtype="int8",
                   calib_ranges=None, quantize_mode="fake",
                   offline_params=None, offline_out=None):
    """Clone `sym` with int8 boundaries on every quantizable node.

    quantize_mode='fake': quantize_v2 -> dequantize pairs on data/weight
    inputs (values ride the int8 grid, compute stays fp32).
    quantize_mode='full': the node itself becomes the int8 kernel
    (quantized_fully_connected / quantized_conv, int32 accumulation)
    followed by dequantize — requires calib_ranges for the data input.

    calib_ranges: optional {(producer_name, slot): (min, max)} from
    calibration; quantize_v2 nodes without a range compute min/max at
    runtime (the reference's non-calibrated mode).

    offline_params: {var_name: numpy array} — in full mode, weight/bias
    variables in this dict are quantized OFFLINE (the reference's
    quantize-params step): their quantize nodes become plain
    '<name>_int8'/'_int8_min'/'_int8_max' variables whose values are
    written into `offline_out`, so inference never re-quantizes weights.
    """
    from ..symbol.symbol import Symbol, _Node, Variable

    if quantize_mode not in ("fake", "full"):
        raise MXNetError(f"quantize_mode must be fake|full, "
                         f"got {quantize_mode!r}")
    excluded = set(excluded_sym_names)
    mapping = {}
    offline_params = offline_params or {}

    def make_quant(name, src, dtype="int8", key=None):
        params = {"out_type": dtype}
        if calib_ranges and key in calib_ranges:
            lo, hi = calib_ranges[key]
            params["min_calib_range"] = float(lo)
            params["max_calib_range"] = float(hi)
        return _Node("_contrib_quantize_v2", name, params=params,
                     inputs=[src])

    def make_offline(var_name, key):
        """Quantize a parameter now (symmetric int8, same math as
        quantize_v2) and emit variables carrying the results."""
        a = np.asarray(offline_params[var_name], np.float32)
        if calib_ranges and key in calib_ranges:
            lo, hi = calib_ranges[key]
        else:
            lo, hi = float(a.min()), float(a.max())
        real = max(abs(lo), abs(hi), 1e-20)
        q = np.clip(np.round(a * (127.0 / real)), -127, 127) \
            .astype(np.int8)
        base = f"{var_name}_int8"
        if offline_out is not None:
            offline_out[base] = q
            offline_out[base + "_min"] = np.float32(-real)
            offline_out[base + "_max"] = np.float32(real)
        nodes = [Variable(base)._outputs[0][0],
                 Variable(base + "_min")._outputs[0][0],
                 Variable(base + "_max")._outputs[0][0]]
        # mimic a quantize node's (values, min, max) output triple

        class _Triple:
            pass

        t = _Triple()
        t.slots = [(nodes[0], 0), (nodes[1], 0), (nodes[2], 0)]
        return t

    def cloned(node):
        if id(node) in mapping:
            return mapping[id(node)]
        new = _Node(node.op, node.name, params=dict(node.params),
                    attrs=dict(node.attrs))
        new.aux_mark = node.aux_mark
        mapping[id(node)] = new
        new.inputs = [(cloned(n), s) for n, s in node.inputs]
        if node.op not in _QUANTIZABLE or node.name in excluded:
            return new
        if quantize_mode == "full":
            # replace with the real int8 kernel + boundary dequantize.
            # Range keys use the ORIGINAL producer name — a chained
            # quantizable producer's clone is its '<name>_dequantize'
            # node, which calibration never saw.
            qslots = []  # per input: [(node, slot) x3] = values/min/max
            for i, ((src_node, src_slot), (orig_src, orig_slot)) in \
                    enumerate(zip(new.inputs[:3], node.inputs[:3])):
                key = (orig_src.name, orig_slot)
                if orig_src.is_var and orig_src.name in offline_params:
                    qslots.append(make_offline(orig_src.name, key).slots)
                else:
                    q = make_quant(f"{node.name}_in{i}_quantize",
                                   (src_node, src_slot), quantized_dtype,
                                   key=key)
                    qslots.append([(q, 0), (q, 1), (q, 2)])
            d, w = qslots[0], qslots[1]
            b = qslots[2] if len(qslots) > 2 else qslots[1]
            inputs = [d[0], w[0], b[0], d[1], d[2], w[1], w[2], b[1], b[2]]
            qparams = {k: node.params[k]
                       for k in _FULL_PARAMS[node.op]
                       if k in node.params}
            if len(qslots) <= 2:
                qparams["no_bias"] = True
            qnode = _Node(_FULL_OPS[node.op], f"{node.name}_int8",
                          params=qparams, inputs=inputs)
            dq = _Node("_contrib_dequantize", f"{node.name}_dequantize",
                       inputs=[(qnode, 0), (qnode, 1), (qnode, 2)])
            # downstream consumers see this dequantized fp32 value
            mapping[id(node)] = dq
            return dq
        # fake-quant: wrap data (slot 0) and weight (slot 1)
        for i in range(min(2, len(new.inputs))):
            src_node, src_slot = new.inputs[i]
            orig_src, orig_slot = node.inputs[i]
            q = make_quant(f"{node.name}_in{i}_quantize",
                           (src_node, src_slot), quantized_dtype,
                           key=(orig_src.name, orig_slot))
            dq = _Node("_contrib_dequantize",
                       f"{node.name}_in{i}_dequantize",
                       inputs=[(q, 0), (q, 1), (q, 2)])
            new.inputs[i] = (dq, 0)
        return new

    outputs = [(cloned(n), s) for n, s in sym._outputs]
    return Symbol(outputs)


def _quant_targets(sym):
    """(producer_name, slot) keys needing ranges: data, weight, and (for
    the full-int8 kernels) bias inputs of every quantizable node."""
    targets = set()
    for node in sym._topo_nodes():
        if node.op in _QUANTIZABLE:
            for n, s in node.inputs[:3]:
                targets.add((n.name, s))
    return targets


def _monitor_names(targets):
    """Executor monitor names outputs "<node>_output[<i>]"."""
    return {(f"{name}_output" if slot == 0 else f"{name}_output{slot}"):
            (name, slot) for name, slot in targets}


def _calibration_forward(sym, arg_params, aux_params, data_names,
                         label_names, calib_data, num_calib_examples,
                         tap, on_batch=None):
    """Shared calibration loop: bind once with a monitor callback, feed
    each calib batch (labels synthesized as zeros), honor the example
    cutoff. `tap(mon_name, arr)` observes every node output; `on_batch`
    observes the raw input batch. Returns the number of examples seen."""
    from .. import context as ctx_mod

    seen = 0
    ex = None
    calib_data.reset()
    for batch in calib_data:
        if on_batch is not None:
            on_batch(batch)
        if ex is None:  # bind once; later batches just feed new inputs
            args = dict(arg_params)
            for n, d in zip(data_names, batch.data):
                args[n] = d
            for ln in label_names or ():
                if ln in sym.list_arguments() and ln not in args:
                    from ..ndarray import ndarray as _nd

                    args[ln] = _nd.zeros((batch.data[0].shape[0],))
            ex = sym.bind(ctx_mod.current_context(), args,
                          aux_states=dict(aux_params) if aux_params
                          else None)
            ex.set_monitor_callback(tap, monitor_all=True)
            ex.forward(is_train=False)
        else:
            ex.forward(is_train=False,
                       **{n: d for n, d in zip(data_names, batch.data)})
        seen += batch.data[0].shape[0]
        _STATS["calib_batches"] += 1
        if num_calib_examples is not None and seen >= num_calib_examples:
            break
    return seen


def _observed(arr):
    """Concrete array of one observed tensor: NDArrays resolve through
    ``_force()`` (a lazy bulk-segment placeholder must be flushed before
    device math can see it), raw arrays pass through."""
    if hasattr(arr, "_force"):
        return arr._force()
    return arr._data if hasattr(arr, "_data") else arr


def _device_minmax(arr):
    """(min, max) of one observed tensor with ONE small device->host
    pull: the reduction runs on device and only the scalar pair crosses
    to the host — never the full activation."""
    import jax.numpy as jnp

    a = _observed(arr)
    if isinstance(a, np.ndarray):
        _STATS["calib_tensor_syncs"] += 1
        return float(a.min()), float(a.max())
    pair = np.asarray(jnp.stack([jnp.min(a), jnp.max(a)]))
    _STATS["calib_tensor_syncs"] += 1
    return float(pair[0]), float(pair[1])


def _device_abs_hist(arr, hi, num_bins):
    """|activation| histogram of one observed tensor, accumulated on
    device; only the ``num_bins`` counts cross to the host (one sync per
    monitored tensor per batch — the eager-replay calibration cost fix
    from PERF.md round 5)."""
    import jax.numpy as jnp

    a = _observed(arr)
    if isinstance(a, np.ndarray):
        _STATS["calib_tensor_syncs"] += 1
        return np.histogram(np.abs(a).ravel(), bins=num_bins,
                            range=(0.0, hi))[0].astype(np.int64)
    counts, _edges = jnp.histogram(jnp.abs(a).ravel(), bins=num_bins,
                                   range=(0.0, hi))
    _STATS["calib_tensor_syncs"] += 1
    return np.asarray(counts).astype(np.int64)


def _collect_ranges(sym, arg_params, aux_params, data_names, label_names,
                    calib_data, num_calib_examples, logger=None,
                    seen_out=None):
    """Naive calibration: run the fp32 graph over calib batches recording
    per-producer min/max (contrib/quantization.py _LayerOutputCollector).
    Reductions run on device; only scalar pairs cross to the host.
    ``seen_out`` (a list) receives the example count when given."""
    targets = _quant_targets(sym)
    name_of = _monitor_names(targets)
    ranges = {}

    def _expand(key, pair):
        lo, hi = ranges.get(key, (np.inf, -np.inf))
        ranges[key] = (min(lo, pair[0]), max(hi, pair[1]))

    def tap(mon_name, arr):
        key = name_of.get(mon_name)
        if key is not None:
            _expand(key, _device_minmax(arr))

    # range of weights/vars straight from params
    for (name, slot) in targets:
        if name in arg_params:
            a = arg_params[name].asnumpy()
            ranges[(name, slot)] = (float(a.min()), float(a.max()))

    def on_batch(batch):
        for n, d in zip(data_names, batch.data):
            _expand((n, 0), _device_minmax(d))

    with _calib_timer():
        seen = _calibration_forward(sym, arg_params, aux_params,
                                    data_names, label_names, calib_data,
                                    num_calib_examples, tap, on_batch)
    if seen_out is not None:
        seen_out.append(seen)
    return ranges


def _entropy_threshold(hist, edges, num_quantized_bins=255):
    """KL-divergence-optimal clip threshold over an |activation| histogram
    (src/operator/quantization/calibrate.cc ComputeEntropy; same algorithm
    as TensorRT's calibrator). Returns the threshold value."""
    nbins = len(hist)
    half = (num_quantized_bins + 1) // 2
    if nbins <= half:
        return float(edges[-1])
    hist = hist.astype(np.float64)

    def smooth(d, eps=1e-4):
        # calibrate.cc SmoothDistribution: move eps into empty bins so the
        # KL penalty for mass the candidate cannot represent is counted
        # instead of masked away
        is_zero = d == 0
        n_zero = int(is_zero.sum())
        n_nonzero = d.size - n_zero
        if n_nonzero == 0:
            return None
        if n_zero == 0:
            return d
        eps1 = eps * n_zero / n_nonzero
        if eps1 >= 1.0:
            return None
        out = d.copy()
        out[is_zero] = eps
        out[~is_zero] -= eps1
        return out

    best_kl, best_i = np.inf, nbins
    for i in range(half, nbins + 1):
        # reference distribution: clip everything beyond bin i into bin i-1
        p = hist[:i].copy()
        p[i - 1] += hist[i:].sum()
        is_nonzero = hist[:i] > 0
        # candidate: quantize the first i bins into `half` levels, then
        # expand back over the nonzero support
        q = np.zeros(i, np.float64)
        group = i / half
        for j in range(half):
            lo = int(np.floor(j * group))
            hi = int(np.floor((j + 1) * group)) if j < half - 1 else i
            seg = slice(lo, max(hi, lo + 1))
            total = hist[seg].sum()
            nz = is_nonzero[seg].sum()
            if nz:
                q[seg] = np.where(is_nonzero[seg], total / nz, 0.0)
        # smooth the raw COUNT distributions (calibrate.cc order: counts
        # are >= 1 wherever nonzero, so eps never drives a bin negative),
        # normalize afterwards
        p = smooth(p)
        q = smooth(q)
        if p is None or q is None:
            continue
        p /= p.sum()
        q /= q.sum()
        mask = p > 0
        kl = float(np.sum(p[mask] * np.log(p[mask] / q[mask])))
        if kl < best_kl:
            best_kl, best_i = kl, i
    return float(edges[best_i])


def _collect_entropy_ranges(sym, arg_params, aux_params, data_names,
                            label_names, calib_data, num_calib_examples,
                            num_bins=None, logger=None, seen_out=None):
    """Two passes: (1) max|activation| per target via the naive collector,
    (2) |activation| histograms, then the KL threshold per target.
    Weight/bias params keep exact min/max (the reference also only
    entropy-calibrates activations). Histograms accumulate ON DEVICE —
    each monitored tensor costs one ``num_bins``-count pull per batch,
    not a full-activation transfer per histogram (PERF.md round 5's
    eager-replay calibration cost)."""
    num_bins = _calib_bins(num_bins)
    naive = _collect_ranges(sym, arg_params, aux_params, data_names,
                            label_names, calib_data, num_calib_examples,
                            logger, seen_out=seen_out)
    param_keys = {k for k in naive if k[0] in arg_params}
    act_keys = [k for k in naive if k not in param_keys]
    max_abs = {k: max(abs(naive[k][0]), abs(naive[k][1]), 1e-20)
               for k in act_keys}
    hists = {k: np.zeros(num_bins, np.int64) for k in act_keys}
    name_of = _monitor_names(act_keys)

    def add_hist(key, arr):
        hists[key] += _device_abs_hist(arr, max_abs[key], num_bins)

    def tap(mon_name, arr):
        key = name_of.get(mon_name)
        if key is not None:
            add_hist(key, arr)

    def on_batch(batch):
        for n, d in zip(data_names, batch.data):
            if (n, 0) in hists:
                add_hist((n, 0), d)

    with _calib_timer():
        _calibration_forward(sym, arg_params, aux_params, data_names,
                             label_names, calib_data, num_calib_examples,
                             tap, on_batch)

        ranges = dict(naive)  # params keep exact min/max
        for k in act_keys:
            edges = np.linspace(0.0, max_abs[k], num_bins + 1)
            t = _entropy_threshold(hists[k], edges)
            ranges[k] = (-t, t)
            if logger:
                logger.info(
                    "entropy calib %s: max|x| %.4f -> threshold %.4f",
                    k, max_abs[k], t)
    return ranges


def symbol_digest(sym):
    """Structural digest of a Symbol: the graph JSON with gensym'd
    op-node names canonicalized (``fullyconnected0`` vs
    ``fullyconnected1`` across builds of the same block), variable names
    kept (they bind the params). One shared helper so the serving
    Predictor's AOT fingerprint and CalibrationTable model-identity use
    THE SAME notion of "same model"."""
    graph = json.loads(sym.tojson())
    for i, node in enumerate(graph.get("nodes", ())):
        if node.get("op") != "null":
            node["name"] = f"n{i}"
    return hashlib.sha256(
        json.dumps(graph, sort_keys=True).encode()).hexdigest()[:16]


class CalibrationMismatchError(MXNetError):
    """A CalibrationTable does not belong to the model it is being
    applied to — different graph structure, missing thresholds, or
    drifted parameter ranges. Raised instead of quantizing with stale
    scales: mis-calibrated int8 answers are silently wrong, an error is
    recoverable. Structured: ``model_digest`` (table's vs model's),
    ``missing`` (quantization targets without thresholds), ``drifted``
    (params whose current range left the table's)."""

    def __init__(self, msg, model_digest=None, missing=(), drifted=()):
        super().__init__(msg)
        self.model_digest = model_digest
        self.missing = tuple(missing)
        self.drifted = tuple(drifted)


class CalibrationTable:
    """Shippable calibration result: per-tensor thresholds + calibration
    provenance, saved as JSON next to the params file so serving hosts
    quantize WITHOUT calibration data (docs/quantization.md).

    ``thresholds``: ``{(producer_name, slot): (min, max)}`` — the keys
    :func:`quantize_model` consumes as ``calib_ranges``. ``model_digest``
    pins the table to the graph it was calibrated on (the BN-FOLDED
    graph, when folding is part of the deploy flow)."""

    VERSION = 1

    def __init__(self, thresholds, calib_mode, num_examples=0,
                 quantized_dtype="int8", model_digest=None, num_bins=None):
        self.thresholds = {tuple(k): (float(v[0]), float(v[1]))
                           for k, v in thresholds.items()}
        self.calib_mode = calib_mode
        self.num_examples = int(num_examples)
        self.quantized_dtype = quantized_dtype
        self.model_digest = model_digest
        self.num_bins = num_bins

    def digest(self):
        """Digest of the quantization-relevant content (thresholds +
        mode + dtype): the AOT compile-cache ingredient — a recalibrated
        table can never false-hit a stale compiled program."""
        blob = json.dumps({
            "thresholds": sorted((f"{n}:{s}", lo, hi) for (n, s), (lo, hi)
                                 in self.thresholds.items()),
            "calib_mode": self.calib_mode,
            "quantized_dtype": self.quantized_dtype,
        }, sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]

    def to_json(self):
        return json.dumps({
            "version": self.VERSION,
            "calib_mode": self.calib_mode,
            "quantized_dtype": self.quantized_dtype,
            "num_examples": self.num_examples,
            "num_bins": self.num_bins,
            "model_digest": self.model_digest,
            "thresholds": {f"{n}:{s}": [lo, hi] for (n, s), (lo, hi)
                           in sorted(self.thresholds.items())},
        }, sort_keys=True, indent=1)

    def save(self, path):
        from ..resilience.checkpoint import atomic_write_bytes

        atomic_write_bytes(path, self.to_json().encode())
        _STATS["calib_tables_saved"] += 1
        return path

    @classmethod
    def from_json(cls, text):
        d = json.loads(text)
        if d.get("version") != cls.VERSION:
            raise MXNetError(
                f"CalibrationTable version {d.get('version')!r} is not "
                f"supported (expected {cls.VERSION})")
        thresholds = {}
        for key, (lo, hi) in d["thresholds"].items():
            name, _, slot = key.rpartition(":")
            thresholds[(name, int(slot))] = (lo, hi)
        return cls(thresholds, d["calib_mode"],
                   num_examples=d.get("num_examples", 0),
                   quantized_dtype=d.get("quantized_dtype", "int8"),
                   model_digest=d.get("model_digest"),
                   num_bins=d.get("num_bins"))

    @classmethod
    def load(cls, path):
        with open(path) as f:
            table = cls.from_json(f.read())
        _STATS["calib_tables_loaded"] += 1
        return table

    def stale_clone(self):
        """A copy whose model identity is wrong — the shape of a stale
        table shipped against a newer model. Used by the
        ``int8_calib_mismatch`` fault drill (resilience/faults.py) so
        the detection path is exercisable deterministically."""
        clone = CalibrationTable(
            self.thresholds, self.calib_mode, self.num_examples,
            self.quantized_dtype,
            model_digest="0" * 16, num_bins=self.num_bins)
        return clone

    def validate_for(self, sym, arg_params=None, model_digest=None):
        """Threshold-drift detection: raise
        :class:`CalibrationMismatchError` unless this table matches
        ``sym`` — same structural digest (when both sides carry one),
        a threshold for every quantization target, and (when
        ``arg_params`` is given) parameter value ranges still inside the
        table's recorded ranges (a re-trained weight outside its
        calibrated range would silently clip)."""
        digest = model_digest or symbol_digest(sym)
        problems = []
        if self.model_digest is not None and digest != self.model_digest:
            problems.append(
                f"model digest {digest} != table digest "
                f"{self.model_digest}")
        targets = _quant_targets(sym)
        missing = sorted(f"{n}[{s}]" for (n, s) in targets
                         if (n, s) not in self.thresholds)
        if missing:
            problems.append(f"no thresholds for targets {missing}")
        drifted = []
        if arg_params is not None:
            for (n, s) in sorted(targets):
                if n not in arg_params or (n, s) not in self.thresholds:
                    continue
                # on-device reduction, scalar-pair pull — a fleet-replica
                # rebuild must not ship every weight tensor to the host
                # just to drift-check it
                lo, hi = _device_minmax(arg_params[n])
                tlo, thi = self.thresholds[(n, s)]
                span = max(abs(tlo), abs(thi), 1e-20)
                if lo < tlo - 1e-5 * span or hi > thi + 1e-5 * span:
                    drifted.append(f"{n}[{s}] value range ({lo:.6g}, "
                                   f"{hi:.6g}) left calibrated "
                                   f"({tlo:.6g}, {thi:.6g})")
        if drifted:
            problems.append(f"param ranges drifted: {drifted}")
        if problems:
            _STATS["calib_mismatches"] += 1
            raise CalibrationMismatchError(
                "calibration table does not match this model — "
                "re-calibrate instead of serving mis-scaled int8 "
                "answers: " + "; ".join(problems),
                model_digest=self.model_digest, missing=missing,
                drifted=drifted)
        return self


def calibrate(sym, arg_params, aux_params, calib_data,
              calib_mode="entropy", data_names=("data",),
              label_names=("softmax_label",), num_calib_examples=None,
              num_bins=None, logger=None):
    """Run calibration as a standalone product step and return a
    :class:`CalibrationTable` (thresholds + mode + sample count +
    model digest) ready to ``save()`` and ship to serving hosts.

    Calibrate the graph you will DEPLOY: if the serving flow folds
    BatchNorm (``Predictor.quantize`` does), pass the folded symbol —
    the table's model digest pins exactly that graph."""
    if calib_mode not in ("naive", "entropy"):
        raise MXNetError(f"calibrate: calib_mode must be naive|entropy, "
                         f"got {calib_mode!r}")
    collect = (_collect_ranges if calib_mode == "naive"
               else _collect_entropy_ranges)
    kwargs = {} if calib_mode == "naive" else {"num_bins": num_bins}
    seen_out = []
    ranges = collect(sym, arg_params, aux_params, data_names, label_names,
                     calib_data, num_calib_examples, logger=logger,
                     seen_out=seen_out, **kwargs)
    return CalibrationTable(ranges, calib_mode,
                            num_examples=seen_out[0] if seen_out else 0,
                            num_bins=_calib_bins(num_bins)
                            if calib_mode == "entropy" else None,
                            model_digest=symbol_digest(sym))


def quantize_model(sym, arg_params, aux_params, data_names=("data",),
                   label_names=("softmax_label",), excluded_sym_names=(),
                   calib_mode="none", calib_data=None,
                   num_calib_examples=None, quantized_dtype="int8",
                   quantize_mode="fake", calib_table=None, logger=None):
    """Quantize a symbolic model (contrib/quantization.py:quantize_model).

    calib_mode: 'none' (runtime min/max), 'naive' (min/max over
    calib_data), or 'entropy' (KL-optimal clip thresholds,
    calibrate.cc). quantize_mode: 'fake' (int8 grid, fp32 compute) or
    'full' (real int8 kernels, int32 MXU accumulation — requires
    calibration). ``calib_table`` (a :class:`CalibrationTable` or a path
    to a saved one) supplies thresholds WITHOUT calibration data — it is
    validated against the model first (stale table -> structured
    :class:`CalibrationMismatchError`, never silent accuracy loss).
    Returns (quantized_symbol, arg_params, aux_params).
    """
    if quantized_dtype not in ("int8", "uint8"):
        raise MXNetError("quantized_dtype must be int8 or uint8")
    ranges = None
    if calib_table is not None and calib_data is not None:
        # never silently prefer one: a stale configured table shadowing
        # fresh calibration data is exactly the silent-accuracy-loss
        # class the table validation exists to prevent
        raise MXNetError(
            "quantize_model: pass calib_table OR calib_data, not both "
            "(a pre-shipped table and a fresh calibration run cannot "
            "both win)")
    if calib_table is not None:
        if isinstance(calib_table, str):
            calib_table = CalibrationTable.load(calib_table)
        # the int8_calib_mismatch chaos drill swaps in a stale clone
        # here, proving validation catches it on the REAL apply path
        calib_table = _faults.maybe_calib_table_drift(calib_table)
        calib_table.validate_for(sym, arg_params=arg_params)
        ranges = dict(calib_table.thresholds)
    elif calib_mode in ("naive", "entropy"):
        if calib_data is None:
            raise MXNetError(f"calib_mode={calib_mode!r} requires "
                             "calib_data")
        collect = (_collect_ranges if calib_mode == "naive"
                   else _collect_entropy_ranges)
        ranges = collect(sym, arg_params, aux_params, data_names,
                         label_names, calib_data, num_calib_examples,
                         logger=logger)
    elif calib_mode != "none":
        raise MXNetError(f"unsupported calib_mode {calib_mode!r} "
                         "(supported: 'none', 'naive', 'entropy')")
    if quantize_mode == "full" and ranges is None:
        raise MXNetError("quantize_mode='full' requires calibration "
                         "(calib_mode 'naive' or 'entropy')")
    if quantize_mode == "full" and quantized_dtype != "int8":
        raise MXNetError("quantize_mode='full' kernels are symmetric "
                         "int8; use quantized_dtype='int8'")
    if quantize_mode == "full":
        # quantize weights/biases OFFLINE (the reference's params step):
        # inference graphs carry int8 params, not per-step re-quantization
        from ..ndarray import ndarray as _nd

        offline_in = {k: v.asnumpy() for k, v in arg_params.items()}
        offline_out = {}
        qsym = quantize_graph(sym, excluded_sym_names, quantized_dtype,
                              ranges, quantize_mode=quantize_mode,
                              offline_params=offline_in,
                              offline_out=offline_out)
        # integer-grid propagation: pool/relu/residual-add boundaries stay
        # int8; requantize replaces quantize(dequantize(int32)) chains
        qsym = _int8_grid_propagate(qsym)
        new_args = {k: _nd.array(v, dtype=v.dtype)
                    for k, v in offline_out.items()}
        live = set(qsym.list_arguments())
        for k, v in arg_params.items():
            if k in live:  # fp32 params still consumed (e.g. excluded ops)
                new_args[k] = v
        return qsym, new_args, aux_params
    qsym = quantize_graph(sym, excluded_sym_names, quantized_dtype, ranges,
                          quantize_mode=quantize_mode)
    return qsym, arg_params, aux_params


# ---------------------------------------------------------------------------
# round 5: whole-graph int8 — BN folding + integer-grid propagation, so a
# quantized ResNet stays on the int8 grid through pool / relu / residual-add
# instead of bouncing through dequantize at every boundary
# (reference: src/operator/quantization/quantized_{pooling,activation,
# elemwise_add}.cc + the BN-fold every deployed int8 CNN applies)
# ---------------------------------------------------------------------------

def fold_batch_norm(sym, arg_params, aux_params, eps_default=1e-3):
    """Fold inference-mode BatchNorm into the preceding Convolution.

    conv -> BN(gamma, beta, mean, var) becomes conv' with
      w' = w * gamma / sqrt(var + eps)   (per output channel)
      b' = (b - mean) * gamma / sqrt(var + eps) + beta
    Returns (new_sym, new_arg_params, new_aux_params). Only BN nodes whose
    sole input is a Convolution output are folded; others stay (their
    moving stats remain in aux). The fold is exact for inference
    (use_global_stats semantics)."""
    from ..ndarray import ndarray as _nd
    from ..symbol.symbol import Symbol, _Node

    args = {k: (v.asnumpy() if hasattr(v, "asnumpy") else np.asarray(v))
            for k, v in arg_params.items()}
    auxs = {k: (v.asnumpy() if hasattr(v, "asnumpy") else np.asarray(v))
            for k, v in aux_params.items()}
    mapping = {}

    def var_of(node_inputs, idx):
        n, _ = node_inputs[idx]
        return n.name if n.is_var else None

    def cloned(node):
        if id(node) in mapping:
            return mapping[id(node)]
        new = _Node(node.op, node.name, params=dict(node.params),
                    attrs=dict(node.attrs))
        new.aux_mark = node.aux_mark
        mapping[id(node)] = new
        new.inputs = [(cloned(n), s) for n, s in node.inputs]
        if node.op != "BatchNorm":
            return new
        src, src_slot = node.inputs[0]
        if src.is_var or src.op != "Convolution" or src_slot != 0:
            return new
        gamma_n = var_of(node.inputs, 1)
        beta_n = var_of(node.inputs, 2)
        mean_n = var_of(node.inputs, 3)
        var_n = var_of(node.inputs, 4)
        w_n = var_of(src.inputs, 1)
        if None in (gamma_n, beta_n, mean_n, var_n, w_n) or \
                w_n not in args or mean_n not in auxs:
            return new
        eps = float(node.params.get("eps", eps_default))
        fix_gamma = bool(node.params.get("fix_gamma", True))
        gamma = (np.ones_like(auxs[mean_n]) if fix_gamma
                 else args[gamma_n])
        beta = args[beta_n]
        mean, var = auxs[mean_n], auxs[var_n]
        scale = gamma / np.sqrt(var + eps)
        w = args[w_n]
        layout = src.params.get("layout")
        # weight layouts: OIHW (channels-first) and OHWI (channels-last)
        # both keep O on axis 0
        args[w_n + "_bnfold"] = (
            w * scale.reshape((-1,) + (1,) * (w.ndim - 1))).astype(w.dtype)
        b_prev = 0.0
        bias_n = var_of(src.inputs, 2) if len(src.inputs) > 2 else None
        if bias_n is not None and bias_n in args:
            b_prev = args[bias_n]
        args[w_n + "_bnfold_bias"] = (
            (b_prev - mean) * scale + beta).astype(beta.dtype)
        conv_clone = cloned(src)  # already cloned as new.inputs[0]
        from ..symbol.symbol import Variable as _Var

        wv = _Var(w_n + "_bnfold")._outputs[0][0]
        bv = _Var(w_n + "_bnfold_bias")._outputs[0][0]
        folded = _Node("Convolution", src.name + "_bnfold",
                       params={**src.params, "no_bias": False},
                       inputs=[conv_clone.inputs[0], (wv, 0), (bv, 0)])
        mapping[id(node)] = folded
        return folded

    out_sym = Symbol([(cloned(n), s) for n, s in sym._outputs])
    live_args = set(out_sym.list_arguments())
    new_args = {k: _nd.array(v) for k, v in args.items() if k in live_args}
    live_aux = set(out_sym.list_auxiliary_states())
    new_aux = {k: _nd.array(v) for k, v in auxs.items() if k in live_aux}
    return out_sym, new_args, new_aux


_I32_PRODUCERS = ("_contrib_quantized_conv",
                  "_contrib_quantized_fully_connected",
                  "_contrib_quantized_elemwise_add",
                  "_contrib_quantized_elemwise_mul")
_I8_PRODUCERS = ("_contrib_quantize_v2", "_contrib_requantize")
_GRID_PASSTHROUGH = ("_contrib_quantized_pooling", "_contrib_quantized_act",
                     "_contrib_quantized_flatten")


def _grid_of(node):
    """'int8' / 'int32' / None — which integer grid a node's output rides."""
    seen = set()
    while True:
        if node.is_var or id(node) in seen:
            return None
        seen.add(id(node))
        if node.op in _I32_PRODUCERS:
            return "int32"
        if node.op in _I8_PRODUCERS:
            return "int8"
        if node.op in _GRID_PASSTHROUGH:
            node = node.inputs[0][0]
            continue
        return None


def _int8_grid_propagate(sym):
    """Peephole pass over a full-mode quantized graph: ops that can run on
    the integer grid consume their producer's int8/int32 triple directly.

    - quantize_v2(dequantize(int32 triple))  -> requantize(triple)
    - Pooling(dequantize(int8 triple))       -> quantized_pooling
    - Activation-relu(dequantize(int8))      -> quantized_act
    - elemwise_add(deq(int8), deq(int8))     -> quantized_elemwise_add
    Every rewritten node keeps its original identity as the boundary
    dequantize, so fp32 consumers are untouched; chained int8 consumers
    then fold through THEIR dequantize, and XLA DCEs the dead boundaries.
    """
    from ..symbol.symbol import _Node

    def deq_src(inp):
        n, slot = inp
        if not n.is_var and n.op == "_contrib_dequantize" and slot == 0:
            q, qs = n.inputs[0]
            return n, q
        return None, None

    changed = True
    while changed:
        changed = False
        # one reverse index per pass: producer (node, slot) -> its
        # quantize/requantize consumer (reused by the residual-add fold)
        quant_of = {}
        for n2 in sym._topo_nodes():
            if not n2.is_var and n2.op in _I8_PRODUCERS and n2.inputs:
                quant_of[(id(n2.inputs[0][0]), n2.inputs[0][1])] = n2
        for node in sym._topo_nodes():
            if node.is_var:
                continue
            if node.op == "_contrib_quantize_v2":
                dq, q = deq_src(node.inputs[0])
                if dq is not None and _grid_of(q) == "int32":
                    node.op = "_contrib_requantize"
                    node.inputs = list(dq.inputs)
                    node.params = {k: node.params[k] for k in
                                   ("min_calib_range", "max_calib_range")
                                   if k in node.params}
                    changed = True
            elif node.op == "Pooling":
                dq, q = deq_src(node.inputs[0])
                layout_ok = (node.params.get("layout") or "NCHW")[1] == "C"
                if dq is not None and layout_ok and \
                        _grid_of(q) is not None:
                    qp_params = {k: v for k, v in node.params.items()
                                 if k in ("kernel", "stride", "pad",
                                          "pool_type", "global_pool",
                                          "pooling_convention",
                                          "count_include_pad", "layout")}
                    qp = _Node("_contrib_quantized_pooling",
                               node.name + "_int8",
                               params=qp_params,
                               inputs=list(dq.inputs))
                    node.op = "_contrib_dequantize"
                    node.params = {}
                    node.inputs = [(qp, 0), (qp, 1), (qp, 2)]
                    changed = True
            elif node.op == "Activation" and \
                    node.params.get("act_type", "relu") == "relu":
                dq, q = deq_src(node.inputs[0])
                if dq is not None and _grid_of(q) is not None:
                    qa = _Node("_contrib_quantized_act",
                               node.name + "_int8",
                               params={"act_type": "relu"},
                               inputs=list(dq.inputs))
                    node.op = "_contrib_dequantize"
                    node.params = {}
                    node.inputs = [(qa, 0), (qa, 1), (qa, 2)]
                    changed = True
            elif node.op in ("elemwise_add", "broadcast_add", "_plus"):
                # an operand joins the int8-grid add if it is (a) a
                # dequantize of an int8 triple, (b) a dequantize of an
                # int32 triple (requantized first), or (c) an fp32 value
                # some OTHER consumer already quantizes (the residual-skip
                # case: the next conv's quantize_v2 holds its triple —
                # reuse it instead of quantizing twice)
                def int8_triple(inp):
                    dq, q = deq_src(inp)
                    if dq is not None:
                        g = _grid_of(q)
                        if g == "int8":
                            return list(dq.inputs)
                        if g == "int32":
                            rq = _Node("_contrib_requantize",
                                       q.name + "_rq",
                                       inputs=list(dq.inputs))
                            return [(rq, 0), (rq, 1), (rq, 2)]
                    qn = quant_of.get((id(inp[0]), inp[1]))
                    if qn is not None:
                        return [(qn, 0), (qn, 1), (qn, 2)]
                    return None

                ta = int8_triple(node.inputs[0])
                tb = int8_triple(node.inputs[1])
                if ta is not None and tb is not None:
                    qadd = _Node(
                        "_contrib_quantized_elemwise_add",
                        node.name + "_int8",
                        inputs=[ta[0], tb[0], ta[1], ta[2], tb[1], tb[2]])
                    node.op = "_contrib_dequantize"
                    node.params = {}
                    node.inputs = [(qadd, 0), (qadd, 1), (qadd, 2)]
                    changed = True
    return sym
