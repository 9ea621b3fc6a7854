"""On-chip benchmark of the BLASX library.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on a TPU and prints one JSON
line.  Every piece a cell names is found by file name:

* ``bench/configs/<config>.json`` — the deployment's sizes, with its
  source, ``reduced`` and ``assumed``;
* ``bench/traffic/<traffic>.json`` — the traffic mix: data that the
  loop it names (``"loop"``) reads;
* ``bench/loops/<loop>.py`` — the generator of one kind of loop, on the
  helpers of ``bench/generator.py``; every mix of that kind is data for it;
* ``bench/limits/<cell>.json`` — the limit of each number that decides
  ``correct``, with the readings it was set from;
* ``bench/metrics/<metric>.py`` — one reader per per-layer metric;
* ``bench/routines/<routine>.py`` — a routine's flop count and its plain
  float64 reference;
* ``bench/peaks.json`` — the chip's peaks, keyed by ``device_kind``.
"""
