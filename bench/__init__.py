"""The benchmark of the batched buffer-policy simulator.

``python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the chips of the machine it is
started on.  Everything that belongs to one configuration, traffic mix or
per-layer metric is a file of its own, found by the name the benchmark
gives it:

* ``bench/configs/<config>.json``: the deployment (database, source, cuts);
* ``bench/traffic/<traffic>.json``: the operating point of a cell (streams,
  bandwidth, lanes, horizon, lane shards);
* ``bench/layer_metrics/<metric>.py``: a reader with ``read(ctx)`` that
  returns the metric's value, or ``None`` where it finds nothing to read.

The yardstick lives here too: the traffic generators (``workgen``), the
plain reference the results are compared with (``reference``), the trace
reduction (``trace``), the table of peaks (``peaks``) and the work count of
the kernels (``kernel_work``).
"""
