"""Observability: named host spans and a process-wide counter registry.

Spans.  `span(name, **args)` is `jax.profiler.TraceAnnotation`: it writes
a TraceMe into the profiler's own trace, so every library span lands on
the same clock as the device operations of that trace, and its keyword
arguments become the event's stats.  When no trace is being taken it
costs about a microsecond.  Library spans are named `repro.<area>.<what>`
and sit only at the boundaries of a fit, a tree batch, a level, a stream
chunk, a checkpoint write or a predict call -- never inside a per-leaf
or per-row loop.  `*.fetch` spans are the host waiting on the device;
every other span is host work.

Named scopes.  The device programs name their phases with
`jax.named_scope` (`level.draw`, `level.supersplit[.tables|.score]`,
`level.merge`, `level.reassign`, `level.totals`, `level.partition`,
`level.tree_loop`, `presort.bin_columns`); a scope only changes the
operations' metadata (`op_name`), not the program.

Counters.  `count(name, k)` adds to a named integer or float;
`counter(name)` reads one, `counters()` is a snapshot of all and
`delta(snapshot)` what changed since one.
The registry holds, among others:

  level.dispatches        batched level programs dispatched (build_forest)
  level.tree_dispatches   per-tree level programs dispatched (build_tree)
  level.traces            level programs traced (compilations)
  level.tree_rows         sum over dispatches of trees x rows in the state
  level.table_updates     sum over dispatches of the bin-table scatter's
                          updates: per tree, columns x rows scattered
                          (x S on regression's plane path)
  level.fetch_bytes       bytes of level structs and totals fetched
  stream.chunk_dispatches streamed chunk programs dispatched
  stream.traces           streamed chunk programs traced
  stream.score_traces     streamed scoring programs traced
  predict.traces          forest descent programs traced
  gbt.raw_traces          boosted-model descent programs traced
  ckpt.write_s            seconds spent writing checkpoints
"""
from __future__ import annotations

import threading

import jax

_COUNTERS: dict = {}
_LOCK = threading.Lock()


def span(name: str, **args):
    """A host span `name` in the profiler's trace, with `args` as stats."""
    return jax.profiler.TraceAnnotation(name, **args)


def count(name: str, k=1) -> None:
    """Add `k` (an int, or seconds as a float) to the counter `name`."""
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + k


def counter(name: str):
    """The counter `name` now (0 if never counted)."""
    with _LOCK:
        return _COUNTERS.get(name, 0)


def counters() -> dict:
    """A snapshot of every counter."""
    with _LOCK:
        return dict(_COUNTERS)


def delta(before: dict, after: dict | None = None) -> dict:
    """Each counter's change from the snapshot `before` to `after` (by
    default now); a counter missing from `before` started at 0."""
    after = counters() if after is None else after
    return {k: v - before.get(k, 0) for k, v in after.items()}
