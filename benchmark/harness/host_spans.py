"""Spans around the program's host layers, put on from the benchmark's
side for the length of a traced window (host_spans.json lists them)."""
from __future__ import annotations

import contextlib
import functools
import importlib
import inspect
import json
import os
from typing import List

from benchmark.harness.cells import BENCH_DIR


def _wrap(fn, span: str):
    import jax

    @functools.wraps(fn)
    def spanned(*args, **kwargs):
        with jax.profiler.TraceAnnotation(span):
            return fn(*args, **kwargs)

    return spanned


@contextlib.contextmanager
def installed(skipped: List[str]):
    """Wrap every listed function; put the originals back on exit.
    Targets that cannot be found are appended to ``skipped``."""
    with open(os.path.join(BENCH_DIR, "harness", "host_spans.json")) as f:
        specs = json.load(f)["spans"]
    undo = []
    try:
        for spec in specs:
            try:
                owner = importlib.import_module(spec["module"])
                *parents, name = spec["attr"].split(".")
                for part in parents:
                    owner = getattr(owner, part)
                raw = inspect.getattr_static(owner, name)
            except (ImportError, AttributeError):
                skipped.append(spec["span"])
                continue
            if isinstance(raw, (classmethod, staticmethod)):
                new = type(raw)(_wrap(raw.__func__, spec["span"]))
            else:
                new = _wrap(raw, spec["span"])
            setattr(owner, name, new)
            undo.append((owner, name, raw))
        yield
    finally:
        for owner, name, raw in reversed(undo):
            setattr(owner, name, raw)
