"""The yardstick: everything a later PR may read but not change.

``manifest``  BENCHMARK.json -> one cell's configuration, traffic, metrics
``device``    the chip check, the device record, the peaks table, peak memory
``compiles``  jax's own compile-request / cache-hit counters
``stats``     percentile and spread arithmetic
``traffic``   the one general generator every traffic file is read by
``profile``   the jax profiler around a sub-window, host annotations
``xplane``    reduction of an .xplane.pb to busy / idle / per-op / collectives
``xplane_text``  a trace as XSpace text proto: the fixture, the tests' traces
``layers``    what several per-layer readers share
``cell``      one run of one cell: set-up, window, checks, readers, result

From the program the harness takes one thing: its span records
(``cell.py`` reads ``mxnet_tpu.observability.trace.spans()``). The
system under test is reached through ``benchmarks/models`` (how a
configuration is built) and ``benchmarks/loops`` (how it is driven).
"""
