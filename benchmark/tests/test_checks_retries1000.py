"""``checks_retries1000.py``: ``checks_retries.py``'s rows under the two
limits the 1,000-service mesh needs - on the same drawn artifacts as
``test_checks_retries.py``, sound and doctored - and its false-alarm
arithmetic as asserted numbers, from the cell's own walk."""
import math

import pytest

from benchmark.harness import checks_retries as base
from benchmark.harness import checks_retries1000 as checks
from benchmark.reference import walk_retries as reference
from benchmark.tests.test_checks_outcomes import bump
from benchmark.tests.test_checks_retries import (IN_C, OUT_AC, OUT_AC_SIZE,
                                                 RARE, delay_coin, delayed,
                                                 drawn, judge, served)

CELL = "multitier1000_retry2_served"


@pytest.fixture(scope="module")
def rare1000(tmp_path_factory):
    return drawn(tmp_path_factory, "rare1000", RARE)


@pytest.mark.parametrize("check, base_check, quiet", [
    (checks.conservation, base.conservation, False),
    (checks.precheck, base.precheck, True)])
def test_the_rows_are_the_retry_checks_rows_under_two_limits(
        rare1000, tmp_path, check, base_check, quiet):
    """Name for name and value for value; the limits differ only on the
    bands (11 -> 12 digits) and on the delay coin's row (1 -> 3)."""
    mine, wrong, count, events = judge(check, rare1000, tmp_path, quiet=quiet)
    theirs, _, count0, events0 = judge(base_check, rare1000, tmp_path,
                                       quiet=quiet)
    assert wrong == set() and (count, events) == (count0, events0)
    assert [(n, v, op) for n, v, op, _ in mine] == [
        (n, v, op) for n, v, op, _ in theirs]
    moved = {n: (a, b) for (n, _, _, a), (_, _, _, b) in zip(mine, theirs)
             if a != b}
    bands = {n for n, _, _, _ in mine if n.endswith("_digits")}
    assert len(bands) == (5 if not quiet else 6)
    want = {n: (12, 11) for n in bands}
    if quiet:
        want["precheck.executions_outside_buckets"] = (3, 1)
    assert moved == want


def test_three_planted_delay_coins_pass_and_four_do_not(rare1000, tmp_path):
    """A pre-check of the cell meets one delay coin in 53 and four in
    190 million: with three planted no compared number passes its limit
    (``checks_retries.py`` fails the same artifacts), with four the
    bucket row does."""
    compared, wrong, _, _ = judge(checks.precheck, rare1000, tmp_path,
                                  quiet=True, doctor=delay_coin(3),
                                  doc_edit=delayed)
    assert wrong == set()
    by_name = {c[0]: c[1] for c in compared}
    assert by_name["precheck.executions_outside_buckets"] == 3
    assert by_name["precheck.min_latency_rel_gap"] < 1e-9
    _, wrong, _, _ = judge(base.precheck, rare1000, tmp_path, quiet=True,
                           doctor=delay_coin(3), doc_edit=delayed)
    assert wrong == {"precheck.executions_outside_buckets"}
    _, wrong, _, _ = judge(checks.precheck, rare1000, tmp_path, quiet=True,
                           doctor=delay_coin(4), doc_edit=delayed)
    assert wrong == {"precheck.executions_outside_buckets"}


def lost_retries(n):
    """``n`` of c's 500s under a that no attempt followed."""
    return ([bump(OUT_AC, -n), bump(OUT_AC_SIZE, -n * 128), bump(IN_C, -n)]
            + served("c", "200", -n))


def test_a_planted_lost_retry_fails_the_exhausted_tail(rare1000, tmp_path):
    """A lost retry IS an exhausted call as far as one run's totals can
    tell: three, where 20,000 calls expect 2e-5, fail
    ``worst_exhausted_tail_digits`` at 12 digits as at 11; one reads the
    digits of one exhausted call, under the limit."""
    compared, wrong, _, _ = judge(checks.conservation, rare1000, tmp_path,
                                  quiet=False, doctor=lost_retries(3))
    assert wrong == {"worst_exhausted_tail_digits"}
    digits = {c[0]: c[1] for c in compared}["worst_exhausted_tail_digits"]
    assert digits > checks.DIGITS_LIMIT + 1
    compared, wrong, _, _ = judge(checks.conservation, rare1000, tmp_path,
                                  quiet=False, doctor=lost_retries(1))
    assert wrong == set()
    digits = {c[0]: c[1] for c in compared}["worst_exhausted_tail_digits"]
    assert 4 < digits < checks.DIGITS_LIMIT


@pytest.mark.parametrize("check", [checks.conservation, checks.precheck])
def test_a_missing_artifact_is_a_problem(rare1000, check):
    _, ref, by_quiet = rare1000
    assert check(None, None, ref, 20_000)[1:] == (
        ["missing artifact (Fortio JSON or exposition)"], 0, 0)
    assert check(by_quiet[True][0], None, ref, 20_000)[2:] == (0, 0)


def poisson_tail(lam, k):
    """P(at least k events)."""
    return 1.0 - sum(math.exp(-lam) * lam ** j / math.factorial(j)
                     for j in range(k))


def test_the_false_alarm_arithmetic_at_a_thousand_services():
    """The docstring's counts, from the cell's own walk: the tails a
    call and a pre-check compare, what 11 and 12 digits make of a whole
    check, and the delay coin's law at 243,136 x 1,000.1 executed hops."""
    from benchmark.harness import cells

    cell = cells.load_cell(CELL)
    ref = reference.walk(cell.graph, cell.config["model"])
    capable = sum(1 for s in ref.services.values() if s.p > 0)
    spread = sum(1 for s in ref.services.values() if s.ok_min_s < s.ok_max_s)
    assert (capable, spread) == (999, 453)
    a_call = capable * 2 + capable * 2 + 2 + 2 + 1
    a_precheck = a_call + 1 + spread * 2
    assert (a_call, a_precheck) == (4001, 4908)
    runs, calls = 14, 70
    tails = runs * (calls * a_call + a_precheck)
    assert tails * 10.0 ** -base.DIGITS_LIMIT > 1e-5      # 4.0e-5
    assert tails * 10.0 ** -checks.DIGITS_LIMIT < 1e-5 / 2  # 4.0e-6
    assert checks.DIGITS_LIMIT == base.DIGITS_LIMIT + 1
    # the delay coin: 29 blocks of 8,384 requests, 1,000.1 hops each
    assert ref.hops == pytest.approx(1000.0999, abs=1e-3)
    lam = 7.81e-11 * 29 * 8384 * ref.hops
    assert lam == pytest.approx(0.0190, rel=0.01)
    assert 1 / poisson_tail(lam, 1) == pytest.approx(53, rel=0.02)
    assert poisson_tail(lam, 2) == pytest.approx(1.8e-4, rel=0.03)
    # allowing two leaves three coins: over the 1e-6 asked of a
    # pre-check; allowing three leaves four
    assert poisson_tail(lam, checks.DELAYED_HOPS) > 1e-6
    assert poisson_tail(lam, checks.DELAYED_HOPS + 1) == pytest.approx(
        5.4e-9, rel=0.05)
    # a delayed execution that is itself a 500: its ~24-execution series
    # moves its mean by over 1e-2 where the wait passes 0.24 CPU times
    delayed_500 = lam * (ref.hops - 1000) / ref.hops * math.exp(-0.24)
    assert delayed_500 == pytest.approx(1.5e-6, rel=0.05)
    a_check = runs * (calls * a_call * 1e-12 + a_precheck * 1e-12
                      + poisson_tail(lam, 4) + delayed_500)
    assert a_check == pytest.approx(2.5e-5, rel=0.05)
    # what one exhausted call of the cell reads, and two
    one = checks.outcomes._binomial_tail_digits(1, 243_136, 1e-12)
    two = checks.outcomes._binomial_tail_digits(2, 243_136, 1e-12)
    assert 6.5 < one < 6.7 and two > checks.DIGITS_LIMIT + 1


def test_a_program_that_refuses_the_graph_is_refused_with_the_checks(
        monkeypatch):
    """The module lays the configuration's graph out as it is imported:
    3,997 columns here; where the compiler refuses (the parent of PR
    43), ``cells.load_yardstick`` raises and ``run.py`` exits 1 before
    set-up."""
    import sys

    from benchmark.harness import cells
    from isotope_tpu.compiler import compile as compile_mod

    assert checks.lay_out() == 3997
    config = cells.load_cell(CELL).config

    def refuses(graph, *a, **kw):
        raise compile_mod.HopBudgetExceededError(2_000_000)

    monkeypatch.setattr(compile_mod, "compile_graph", refuses)
    import isotope_tpu.compiler as compiler_pkg
    monkeypatch.setattr(compiler_pkg, "compile_graph", refuses)
    monkeypatch.delitem(sys.modules, checks.__name__)
    with pytest.raises(compile_mod.HopBudgetExceededError):
        cells.load_yardstick(config)
    assert checks.__name__ not in sys.modules
    monkeypatch.setitem(sys.modules, checks.__name__, checks)
