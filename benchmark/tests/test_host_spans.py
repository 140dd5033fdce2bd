import inspect
import os

from benchmark.harness import host_spans
from benchmark.harness.cells import BENCH_DIR


def test_every_listed_target_exists_and_is_put_back():
    from isotope_tpu.models.graph import ServiceGraph
    from isotope_tpu.runner import run as runner
    from isotope_tpu.sim.engine import Simulator

    before = (inspect.getattr_static(ServiceGraph, "from_yaml_file"),
              runner.compile_graph, Simulator.__init__)
    skipped = []
    with host_spans.installed(skipped):
        assert skipped == []
        assert runner.compile_graph is not before[1]
        assert Simulator.__init__ is not before[2]
        # a wrapped classmethod still binds its class
        graph = ServiceGraph.from_yaml_file(
            os.path.join(BENCH_DIR, "topologies", "canonical.yaml"))
        assert len(graph.services) == 4
    assert (inspect.getattr_static(ServiceGraph, "from_yaml_file"),
            runner.compile_graph, Simulator.__init__) == before
