"""Job drivers, one file each, found by the name a cell's file gives under
``engine``. A driver exposes, as module-level functions over a ``Job``
(``benchmark/run.py``):

- ``build(job) -> state``: data and weights from ``job.seed`` on the device,
  the federation (or nodes) at the cell's shapes;
- ``check(job, state)``: the reference comparisons made before the window;
- ``warm(job, state)``: run every program the window will use, once;
- ``reset(job, state)``: back to the seeded start, same executables;
- ``measure(job, state, seconds, tracer) -> dict``: the window —
  ``completions`` (monotonic instants, the window's start first), ``losses``
  (one per round), ``attempted``, ``failed``; in a traced run it brackets a few
  rounds with ``tracer.start()`` / ``tracer.stop()``;
- ``finish(job, state, window)``: checks after the window, and clean-up;
- ``describe(job, state) -> dict``: the shapes the metrics need
  (``rounds``-independent): ``flops_per_round``, ``steps_per_program_run`` (local steps one execution of the round's program holds on one device),
  ``train_nodes``, ``round_program`` and whatever the driver wants printed.

A new way of running a job (chunked, pipelined, asynchronous) is a new file.
"""

from __future__ import annotations

import importlib


def load(name: str):
    return importlib.import_module(f"benchmark.engines.{name}")
