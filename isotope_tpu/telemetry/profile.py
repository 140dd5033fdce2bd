"""XLA trace capture: the backend of ``isotope-tpu telemetry --xla-trace``.

Captures a ``jax.profiler`` trace of warmed summary steps — the same
capture path the sweep runner uses per-run via ``--profile``
(runner/run.py wraps each run in ``jax.profiler.trace``) — readable in
TensorBoard/XProf; the README's "Reading a profile" says what the host
and device planes show.
"""
from __future__ import annotations

import glob
import os
from typing import List, Optional


def build_simulator(topology: Optional[str] = None):
    """A Simulator for ``topology`` (YAML path), or the flagship
    ~120-service tree (the bench headline) when ``None``."""
    from isotope_tpu.sim.engine import Simulator

    if topology is None:
        from __graft_entry__ import _flagship

        compiled = _flagship()
    else:
        from isotope_tpu.compiler import compile_graph
        from isotope_tpu.models.graph import ServiceGraph

        compiled = compile_graph(ServiceGraph.from_yaml_file(topology))
    return Simulator(compiled)


def capture_xla_trace(
    out_dir: str,
    topology: Optional[str] = None,
    num_requests: int = 65_536,
    qps: float = 100_000.0,
    steps: int = 3,
    seed: int = 0,
    sim=None,
) -> List[str]:
    """Capture a profiler trace of ``steps`` warmed summary runs.

    Pass an already-built ``sim`` to skip compiling the topology again
    (the ``telemetry`` command does); otherwise ``topology`` selects the
    graph as in :func:`build_simulator`.  The first run (trace +
    compile) happens OUTSIDE the capture window so the trace shows
    steady-state device work.  Returns the ``*.xplane.pb`` files
    written under ``out_dir``.
    """
    import jax

    from isotope_tpu.sim.config import LoadModel

    if sim is None:
        sim = build_simulator(topology)
    load = LoadModel(kind="open", qps=qps)
    block = min(sim.default_block_size(), num_requests)
    key = jax.random.PRNGKey(seed)

    def step(k):
        return sim.run_summary(load, num_requests, k, block_size=block)

    jax.block_until_ready(step(key).count)  # warm: compile outside capture

    with jax.profiler.trace(out_dir):
        out = None
        for i in range(steps):
            out = step(jax.random.fold_in(key, 1 + i))
        jax.block_until_ready(out.count)

    return glob.glob(
        os.path.join(out_dir, "**", "*.xplane.pb"), recursive=True
    )
