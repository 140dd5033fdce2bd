"""ServiceGraph decode + validation tests.

Coverage mirrors the reference's graph/unmarshal_test.go end-to-end fixture
(defaults inheritance) and validation.go error cases.
"""
import datetime
import functools
import glob
import importlib.util
import random
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


def assert_same_error(got, want):
    assert type(got) is type(want)
    # parser messages differ in wording; the place they point at does not
    mark, want_mark = (getattr(e, "problem_mark", None) for e in (got, want))
    assert (mark is None) == (want_mark is None)
    if mark is not None:
        assert (mark.line, mark.column) == (want_mark.line, want_mark.column)


@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_malformed_text_raises_alike_under_both_loaders(loader_mod, case):
    text = MALFORMED[case]
    with pytest.raises((yaml.YAMLError, ValueError)) as want:
        ServiceGraph.decode(yaml.load(text, Loader=yaml.SafeLoader))
    with pytest.raises((yaml.YAMLError, ValueError)) as got:
        loader_mod.ServiceGraph.from_yaml(text)
    assert_same_error(got.value, want.value)


def test_duplicate_key_resolves_alike_under_both_loaders(loader_mod):
    text = "services:\n- name: a\n  numReplicas: 2\n  numReplicas: 3\n"
    assert loader_mod.ServiceGraph.from_yaml(text).services[0].num_replicas \
        == 3


# -- the direct document builder, against yaml.load (ISSUE 45) ------------

def same(a, b):
    """Equal in value and in type at every leaf: ``1``, ``True``,
    ``1.0`` and ``"1"`` all differ, a NaN equals a NaN, and a mapping's
    keys come in the same order."""
    if type(a) is not type(b):
        return False
    if isinstance(a, dict):
        return len(a) == len(b) and all(
            same(ka, kb) and same(a[ka], b[kb]) for ka, kb in zip(a, b)
        )
    if isinstance(a, list):
        return len(a) == len(b) and all(map(same, a, b))
    return repr(a) == repr(b)


def outcome(load, text):
    """What ``load(text)`` returns, or the error it raises."""
    try:
        return load(text)
    except (yaml.YAMLError, ValueError) as e:
        return e


def assert_loads_like_yaml_load(loader_mod, text, direct):
    """``_load(text)`` returns or raises what ``yaml.load`` does with
    ``SafeLoader``, and the builder (``direct``) or the reference path
    produced it."""
    want = outcome(lambda t: yaml.load(t, Loader=yaml.SafeLoader), text)
    got = outcome(loader_mod._load, text)
    assert loader_mod.loaded_directly() == direct
    if isinstance(want, Exception):
        assert_same_error(got, want)
    else:
        assert same(got, want), (got, want)


def test_same_tells_types_apart():
    assert same({"a": [1, float("nan")]}, {"a": [1, float("nan")]})
    for a, b in ((1, True), (1, 1.0), (1, "1"), (None, "null"),
                 ({1: "a"}, {True: "a"}), ({"a": 1, "b": 2}, {"b": 2, "a": 1}),
                 ([1], [1, 1]), (0.0, -0.0)):
        assert not same(a, b) and same(a, a) and same(b, b)


@pytest.mark.parametrize("path", TOPOLOGIES)
def test_builder_makes_the_topologys_document(loader_mod, path):
    with open(path) as f:
        text = f.read()
    assert_loads_like_yaml_load(loader_mod, text, direct=True)


SCALARS = [
    "12", "1_000", "0x1F", "0o17", "017", "1:30", "-7", ".5", "1e3",
    "1.0e+3", ".inf", "-.INF", ".nan", "~", "null", "", "yes", "No", "ON",
    "off", "true", "2001-12-14", "2001-12-14t21:59:43.10-05:00", "10ms",
    "0.01%", "128 KB", "mock-1", "gr\u00f6\u00dfe-\u670d\u52a1",
]

BLOCK = """\
k: {0}
  {1}
seq:
- {0}
  {1}
? {0}
  {1}
: v
"""

#: a scalar as a mapping's value, a sequence's item and a mapping's key
FORMS = {
    "plain": "k: {0}\nseq:\n- {0}\n? {0}\n: v\n".format,
    "single_quoted": "k: '{0}'\nseq:\n- '{0}'\n'{0}': v\n".format,
    "double_quoted": 'k: "{0}"\nseq:\n- "{0}"\n"{0}": v\n'.format,
    "literal": functools.partial(BLOCK.format, "|"),
    "folded": functools.partial(BLOCK.format, ">"),
    "flow": "{{k: [yes, {0}], seq: {{a: {0}, b: 1}}, ? {0} : v}}\n".format,
}


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("scalar", SCALARS, ids=lambda s: s or "empty")
def test_builder_resolves_scalars_as_yaml_load_does(loader_mod, scalar, form):
    assert_loads_like_yaml_load(loader_mod, FORMS[form](scalar), direct=True)


def random_document(rng, depth=0):
    """A nested dict / list / scalar, from ``rng`` alone."""
    def scalar():
        return rng.choice([
            rng.randrange(-10**6, 10**6), rng.random() * 10**rng.randrange(9),
            float("inf"), float("nan"), True, False, None, "", "12", "yes",
            "~", "a: b", " padded ", "two\nlines\n", "- dash", "#hash",
            "svc-%d" % rng.randrange(100), "%dms" % rng.randrange(100),
            "k" * rng.randrange(1, 200), "na\u00efve \u670d\u52a1",
            datetime.date(2001, 1, 1 + rng.randrange(28)),
        ])
    kind = rng.random()
    if depth < 4 and kind < 0.35:
        return {scalar(): random_document(rng, depth + 1)
                for _ in range(rng.randrange(5))}
    if depth < 4 and kind < 0.7:
        return [random_document(rng, depth + 1)
                for _ in range(rng.randrange(5))]
    return scalar()


@pytest.mark.parametrize("flow_style", [False, None, True])
@pytest.mark.parametrize("seed", range(8))
def test_builder_loads_what_safe_dump_writes(loader_mod, seed, flow_style):
    rng = random.Random(seed)
    doc = [random_document(rng) for _ in range(12)]
    text = yaml.safe_dump(doc, default_flow_style=flow_style,
                          allow_unicode=bool(seed % 2))
    assert_loads_like_yaml_load(loader_mod, text, direct=True)


#: what the builder leaves to yaml.load
REFERENCE_ONLY = {
    "anchor_and_alias": "a: &x [1, 2]\nb: *x\n",
    "merge_key": "base: &b {a: 1}\nd:\n  <<: *b\n  c: 3\n",
    "merge_key_inline": "d:\n  <<: {a: 1}\n  c: 3\n",
    "explicit_tag": "a: !!str 5\n",
    "explicit_tag_on_a_mapping": "a: !!map {b: 1}\n",
    "non_specific_tag": "a: ! 5\n",
    "sequence_as_key": "? [a, b]\n: 1\n",
    "mapping_as_key": "? {a: b}\n: 1\n",
    "value_indicator": "a: =\n",
    "merge_indicator_as_a_value": "a: <<\n",
    "two_documents": "a: 1\n---\nb: 2\n",
    "python_object": "a: !!python/object:os.system {}\n",
    "yaml_directive": "%YAML 1.1\n---\na: 1\n",
    "no_such_date": "a: 2001-13-45\n",
    "empty_text": "",
    "comment_only": "# nothing here\n",
}


@pytest.mark.parametrize("case", sorted(REFERENCE_ONLY))
def test_builder_leaves_the_rest_to_yaml_load(loader_mod, case):
    assert_loads_like_yaml_load(loader_mod, REFERENCE_ONLY[case],
                                direct=False)


@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_malformed_text_takes_the_reference_path(loader_mod, case):
    # "not_a_mapping" is a fine document; ServiceGraph.decode refuses it
    assert_loads_like_yaml_load(loader_mod, MALFORMED[case],
                                direct=case == "not_a_mapping")


def test_load_reports_each_document_apart(loader_mod):
    loader_mod._load("a: 1\n")
    assert loader_mod.loaded_directly()
    loader_mod._load("a: &x 1\n")
    assert not loader_mod.loaded_directly()
    loader_mod._load("a: 1\n")
    assert loader_mod.loaded_directly()
