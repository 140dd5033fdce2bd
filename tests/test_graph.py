"""ServiceGraph decode + validation tests.

Coverage mirrors the reference's graph/unmarshal_test.go end-to-end fixture
(defaults inheritance) and validation.go error cases.
"""
import functools
import glob
import importlib.util
import sys

import pytest
import yaml

from isotope_tpu.models import graph as graph_mod
from isotope_tpu.models.graph import (
    NestedConcurrentCommandError,
    RequestToUndefinedServiceError,
    ServiceGraph,
)
from isotope_tpu.models.script import (
    ConcurrentCommand,
    RequestCommand,
    SleepCommand,
)
from isotope_tpu.models.size import ByteSize
from isotope_tpu.models.svctype import ServiceType

FULL_YAML = """
defaults:
  type: http
  numReplicas: 2
  errorRate: 0.1%
  responseSize: 512
  requestSize: 128
services:
- name: a
- name: b
  type: grpc
  numReplicas: 3
  errorRate: 5%
  responseSize: 1k
- name: c
  isEntrypoint: true
  script:
  - sleep: 100ms
  - call: a
  - call: {service: b, size: 256, probability: 50}
  - - call: a
    - call: b
"""


def test_decode_defaults_inheritance():
    g = ServiceGraph.from_yaml(FULL_YAML)
    a, b, c = g.services

    assert a.name == "a"
    assert a.type == ServiceType.HTTP
    assert a.num_replicas == 2
    assert float(a.error_rate) == pytest.approx(0.001)
    assert a.response_size == 512
    assert a.script == []

    assert b.type == ServiceType.GRPC
    assert b.num_replicas == 3
    assert float(b.error_rate) == pytest.approx(0.05)
    assert b.response_size == 1024

    assert c.is_entrypoint
    assert c.script[0] == SleepCommand(0.1)
    # string-form call inherits default requestSize 128
    assert c.script[1] == RequestCommand(service_name="a", size=ByteSize(128))
    assert c.script[2] == RequestCommand(
        service_name="b", size=ByteSize(256), probability=50
    )
    assert isinstance(c.script[3], ConcurrentCommand)


def test_undefined_callee_rejected():
    with pytest.raises(RequestToUndefinedServiceError):
        ServiceGraph.from_yaml(
            """
services:
- name: a
  script:
  - call: ghost
"""
        )


def test_nested_concurrent_rejected():
    with pytest.raises(NestedConcurrentCommandError):
        ServiceGraph.from_yaml(
            """
services:
- name: a
- name: b
  script:
  - - call: a
    - - call: a
      - call: a
"""
        )


def test_service_requires_name():
    with pytest.raises(ValueError):
        ServiceGraph.from_yaml("services:\n- type: http\n")


def test_canonical_topology(tmp_path):
    g = ServiceGraph.from_yaml_file("examples/topologies/canonical.yaml")
    assert g.service_names() == ["a", "b", "c", "d"]
    (entry,) = g.entrypoints()
    assert entry.name == "d"
    # concurrent first step, then a sequential call
    assert isinstance(entry.script[0], ConcurrentCommand)
    assert entry.script[1].service_name == "b"
    # defaults: 1 KB sizes, 3 rbac policies
    assert g.services[0].response_size == 1024
    assert g.services[0].num_rbac_policies == 3


def test_yaml_roundtrip():
    g = ServiceGraph.from_yaml(FULL_YAML)
    again = ServiceGraph.from_yaml(g.to_yaml())
    assert again.services == g.services


def test_roundtrip_with_overridden_defaults():
    # Regression: a service field explicitly equal to a BUILT-IN default must
    # survive encode/decode when the graph-level default differs.
    g = ServiceGraph.from_yaml(
        """
defaults:
  numReplicas: 3
  responseSize: 10k
services:
- name: a
  numReplicas: 1
  responseSize: 0
- name: b
"""
    )
    again = ServiceGraph.from_yaml(g.to_yaml())
    assert again.services == g.services
    assert again.services[0].num_replicas == 1
    assert int(again.services[0].response_size) == 0
    assert again.services[1].num_replicas == 3


def test_empty_services_key():
    g = ServiceGraph.from_yaml("services:\n")
    assert len(g) == 0


def test_defaults_script_does_not_inherit_request_size():
    # unmarshal.go:30-43: the defaults block is parsed before
    # DefaultRequestCommand is installed, so calls in the defaults script
    # get size 0, not requestSize.
    g = ServiceGraph.from_yaml(
        """
defaults:
  requestSize: 10k
  script:
  - call: a
services:
- name: a
- name: b
"""
    )
    assert g.services[1].script[0].size == 0
    # ...while calls in a service's own script DO inherit requestSize.
    g2 = ServiceGraph.from_yaml(
        """
defaults:
  requestSize: 10k
services:
- name: a
- name: b
  script:
  - call: a
"""
    )
    assert g2.services[1].script[0].size == 10240


def test_strict_int_fields():
    for doc in (
        "services:\n- name: a\n  numReplicas: true\n",
        "services:\n- name: a\n  numReplicas: 2.9\n",
        "defaults:\n  numRbacPolicies: 1.5\nservices:\n- name: a\n",
    ):
        with pytest.raises(ValueError):
            ServiceGraph.from_yaml(doc)


# -- the YAML loader: libyaml where PyYAML has it, SafeLoader where not ----

TOPOLOGIES = sorted(
    glob.glob("examples/topologies/*.yaml")
    + glob.glob("benchmark/topologies/*.yaml")
)

MALFORMED = {
    "unclosed_flow_sequence": "services: [a, b\n",
    "tab_indentation": "services:\n\t- name: a\n",
    "two_mappings_on_a_line": "services: a: b\n",
    "python_object_tag": "services: !!python/object:os.system {}\n",
    "not_a_mapping": "- a\n- b\n",
}


@pytest.fixture(scope="module")
def fallback_mod():
    """models/graph.py imported afresh by a PyYAML without libyaml: the
    choice the module makes at import is what is under test."""
    spec = importlib.util.spec_from_file_location(
        "_graph_without_libyaml", graph_mod.__file__
    )
    mod = importlib.util.module_from_spec(spec)
    with pytest.MonkeyPatch.context() as patch:
        patch.delattr(yaml, "CSafeLoader", raising=False)
        patch.setitem(sys.modules, spec.name, mod)  # dataclasses look it up
        spec.loader.exec_module(mod)
    return mod


@pytest.fixture(params=["chosen", "fallback"])
def loader_mod(request):
    if request.param == "chosen":
        return graph_mod
    return request.getfixturevalue("fallback_mod")


@functools.cache
def _safe_loader_reading(path):
    with open(path) as f:
        return ServiceGraph.decode(yaml.load(f, Loader=yaml.SafeLoader))


def test_loader_choice(fallback_mod):
    assert graph_mod.parses_with_libyaml() == yaml.__with_libyaml__
    if yaml.__with_libyaml__:
        assert graph_mod._LOADER is yaml.CSafeLoader
    assert fallback_mod._LOADER is yaml.SafeLoader
    assert not fallback_mod.parses_with_libyaml()


@pytest.mark.parametrize("path", TOPOLOGIES)
def test_topology_decodes_alike_under_both_loaders(loader_mod, path):
    want = _safe_loader_reading(path)
    got = loader_mod.ServiceGraph.from_yaml_file(path)
    assert got.encode() == want.encode()
    if loader_mod is graph_mod:
        assert got == want  # the dataclasses themselves, not only encode()


@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_malformed_text_raises_alike_under_both_loaders(loader_mod, case):
    text = MALFORMED[case]
    with pytest.raises((yaml.YAMLError, ValueError)) as want:
        ServiceGraph.decode(yaml.load(text, Loader=yaml.SafeLoader))
    with pytest.raises((yaml.YAMLError, ValueError)) as got:
        loader_mod.ServiceGraph.from_yaml(text)
    assert type(got.value) is type(want.value)
    # parser messages differ in wording; the place they point at does not
    mark, want_mark = (getattr(e.value, "problem_mark", None)
                       for e in (got, want))
    assert (mark is None) == (want_mark is None)
    if mark is not None:
        assert (mark.line, mark.column) == (want_mark.line, want_mark.column)


def test_duplicate_key_resolves_alike_under_both_loaders(loader_mod):
    text = "services:\n- name: a\n  numReplicas: 2\n  numReplicas: 3\n"
    assert loader_mod.ServiceGraph.from_yaml(text).services[0].num_replicas \
        == 3
