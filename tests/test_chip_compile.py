"""Compile for the v5e without a v5e (on-chip-measurement guide, §2).

The TPU's compiler is installed here and compiles for a chip that is
described, not attached: what it refuses in this file it would refuse
on the machine with the chip, at no chip time.  Nothing runs, so this
says nothing about results or speed — ``chip_smoke.py`` does that.

The ONLY file that describes a topology: only one process may load the
TPU's library, so the call lives in a module-scoped fixture (never at
import, in a ``skipif`` or a ``parametrize``) and every compile happens
in this process.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from isotope_tpu.compiler import compile_graph
from isotope_tpu.metrics.prometheus import MetricsCollector
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.sim import SimParams, Simulator

TOPOLOGIES = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "examples", "topologies",
)
#: one v5e chip's HBM as libtpu reports it ("Used 32.00G of 15.75G")
HBM_BYTES = 15.75 * 2**30
#: the CLI's defaults (commands/simulate_cmd.py): closed loop, 64
#: connections, capped at 1 M requests
CONNECTIONS = 64
REQUESTS = 1_000_000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding onto one described chip, with the persistent cache off
    around the compiles (an entry written for a described chip cannot
    be read back without one — the next compile would only warn)."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _lower_served(sim, compiled, requests, one_chip, collect=True,
                  **observers):
    """``run_summary``'s program as the CLI's closed loop builds it -
    the block scan with the collector and the trim window - lowered
    for the described chip; its argument list is the single-block
    entry's, plus the two trim-window bounds before the visit / phase
    tables.  ``observers``: ``_get_summary``'s ``attr`` / ``timeline``,
    the program of an observer pass (which carries no collector)."""
    blk = sim.default_block_size() // CONNECTIONS * CONNECTIONS
    fn = sim._get_summary(
        blk, -(-requests // blk), "closed", CONNECTIONS,
        MetricsCollector(compiled) if collect else None, True, sat=False,
        **observers,
    )
    _, a = sim.trace_entry_args(blk, "closed", CONNECTIONS)
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    args = [
        jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
        for x in (*a[:5], scalar, scalar, *a[5:])
    ]
    return fn.lower(*args)


@pytest.mark.parametrize("name, hops, block", [
    ("1000-svc_2000-end.yaml", 1000, 33_554),
    ("tree-111-services.yaml", 111, 302_292),
    # the error coins and the live code masks (PR 34)
    ("realistic-multitier-100-errors.yaml", 100, 335_544),
])
def test_cli_summary_program_compiles_for_v5e(one_chip, name, hops, block):
    """The program ``isotope-tpu simulate <graph> --qps 1000 --duration
    1000s`` runs — the block scan behind ``run_summary`` with the
    collector and the trim window — fits one chip."""
    compiled = compile_graph(
        ServiceGraph.from_yaml_file(os.path.join(TOPOLOGIES, name))
    )
    sim = Simulator(compiled, SimParams())
    assert (compiled.num_hops, sim.default_block_size()) == (hops, block)
    mem = _lower_served(
        sim, compiled, REQUESTS, one_chip).compile().memory_analysis()
    assert 0 < mem.temp_size_in_bytes < HBM_BYTES
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES


def test_recorder_pass_of_svc1000_observed_compiles_for_v5e(one_chip):
    """``svc1000_observed``'s flight-recorder pass (``latency240`` +
    ``--timeline``: 8 blocks of 33,536 x 1,000 hops, 27 windows of 10 s
    for 1,000 services).  Its per-window series are masked column sums
    over the block (PR 47): nothing of (requests x hops x channels) is
    stacked, where the einsums they replaced held three 0.4 GB copies a
    block and the cell 4.79 GB on the chip."""
    compiled = compile_graph(ServiceGraph.from_yaml_file(
        os.path.join(TOPOLOGIES, "1000-svc_2000-end.yaml")))
    sim = Simulator(compiled, SimParams(timeline=True))
    blk = sim.default_block_size() // CONNECTIONS * CONNECTIONS
    assert blk == 33_536
    windows = sim.plan_timeline_windows(8 * blk, 1000.0, 10.0)
    assert windows == (27, 10.0)
    mem = _lower_served(sim, compiled, 240_000, one_chip, collect=False,
                        timeline=windows).compile().memory_analysis()
    assert 0 < mem.temp_size_in_bytes < 1.5e9


@pytest.mark.slow
def test_svc10k_served_program_compiles_for_v5e(one_chip, record_property):
    """``svc10k_served``'s program (``latency240``: 240,000 requests in
    73 blocks of 3,328 x 10,000 hops) with nine tiled levels (PR 42):
    the compile is ~4 x the bucketed plan's (55 s against 13 s on this
    sandbox's CPU, 69 s against ~35 s on the chip's host; PERF.md) and
    its temporaries about half (2.56 against 4.76 GB: no (3,328 x
    27,702) step grids).  Records both, and the lowered text's size,
    for the next change of the encoding or block-size rule."""
    compiled = compile_graph(ServiceGraph.from_yaml_file(
        os.path.join(TOPOLOGIES, "multitier-10000.yaml")))
    sim = Simulator(compiled, SimParams())
    assert [d for d, lvl in enumerate(sim._levels)
            if lvl.tiled is not None] == list(range(3, 12))
    assert sim.default_block_size() // CONNECTIONS * CONNECTIONS == 3328
    lowered = _lower_served(sim, compiled, 240_000, one_chip)
    hlo_chars = len(lowered.as_text())
    mem = lowered.compile().memory_analysis()
    record_property("hlo_chars", hlo_chars)
    record_property("temp_size_in_bytes", mem.temp_size_in_bytes)
    # 1.39 M characters and 2.56 GB when written; the bucketed plan's
    # were 2.88 M and 4.76 GB
    assert hlo_chars < 2_000_000
    assert 0 < mem.temp_size_in_bytes < 3.5e9
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES
