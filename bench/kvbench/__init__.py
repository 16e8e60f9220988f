"""The KVComp serving benchmark's own code: everything that decides a number.

Nothing here imports the program at module import time; the program
(``repro``) is reached only through ``repro.api`` and the ``Server`` it
returns, inside ``kvbench.cell``.  Layout of the benchmark directory:

* ``bench/run.py``                  -- entry point: one cell, one run
* ``bench/configs/<config>.json``   -- model sizes as run, source, cut
* ``bench/archs/<arch>.py``         -- one module per architecture: sizes,
                                       weights, reference, counts
* ``bench/traffic/<traffic>.json``  -- traffic-mix parameters (data only)
* ``bench/metrics/<metric>.py``     -- one reader per per-layer metric
* ``bench/peaks.json``              -- chip peaks keyed by ``device_kind``
* ``bench/trace_reduce.py``         -- profiler trace -> busy/idle/op times
"""
