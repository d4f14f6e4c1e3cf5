"""On-chip benchmark of the disaggregated server.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` drives ``DisaggServer.serve`` open-loop on the chip for one
cell of ``BENCHMARK.json`` and prints one JSON result line. Everything that
belongs to one model configuration, one traffic mix or one metric is a file
of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``   the model as it is run, with its source;
* ``families/<model_type>.py`` the model family the configuration's
  ``model_type`` names: its shapes, its ``ArchConfig``, its float32
  reference and its FLOPs a token (``families/__init__.py``);
* ``workloads/<cell>.json``   the traffic mix (one generator reads them all);
* ``metrics/<metric>.py``     a reader with ``read(run) -> float | None``.

The yardstick lives here too: the traffic generator (``traffic.py``), the
FLOP and byte counts (``flops.py``), the table of peaks (``peaks.py``), the
reduction of a profiler trace (``trace.py``), the dense decoder's float32
reference (``reference.py``) and the comparison that decides ``correct``
(``check.py``).
"""
