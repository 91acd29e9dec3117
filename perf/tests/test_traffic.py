"""The generate traffic files: budgets that cannot keep two slots in
step, a ``deal`` that holds the cards its histograms state, requests
that fit a sequence's rows, and a ``why`` whose numbers are the
file's."""

import itertools
import json
import math
import os
import re

import pytest

from perf.harness import loadgen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w for w in BENCH["workloads"] if "-generate-" in w["name"]]


def _load(*parts):
    with open(os.path.join(ROOT, "perf", *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", CELLS, ids=lambda w: w["traffic"])
def test_generate_traffic(cell):
    traffic = _load("traffic", cell["traffic"] + ".json")
    gen = _load("configs", cell["config"] + ".json")["generate"]
    loadgen.check_deal(traffic)          # deal agrees with both histograms
    budgets = [b for b, _ in traffic["max_tokens"]]
    for a, b in itertools.combinations(budgets, 2):
        assert math.gcd(a, b) == 1, (a, b)
    deal = traffic["deal"]
    # the rows one sequence may hold: its run of pages (the full
    # layer's, where rings hold the other layers')
    rows = int(gen["page_size"]) * int(gen["pages_per_seq"])
    assert max(t + b for t, b in deal) <= rows
    why = traffic["why"]
    longest = max(deal, key=lambda c: c[0] + c[1])
    assert (f"{longest[0]:,} + {longest[1]:,} = {sum(longest):,} of the "
            f"{rows:,} rows") in why
    prompt_mean, answer_mean = re.search(
        r"mean (\d+)[;,)].*?answers .*?mean ([\d.]+)\)", why).groups()
    assert int(prompt_mean) == round(sum(t for t, _ in deal) / len(deal))
    assert float(answer_mean) == round(
        sum(b for _, b in deal) / len(deal), 1)
    assert ", ".join(map(str, sorted(budgets))) in why   # says what they are
    assert "co-prime" in why
    # the clients start one after another, k tokens apart: within the
    # shortest answer, and all of them inside the ramp's first requests
    k = traffic["stagger_tokens"]
    assert 0 < k <= min(budgets)
    assert f"'stagger_tokens' {k}" in why
    # the toy sizes of a rehearsal are a deal of their own
    loadgen.check_deal(traffic["rehearse"])
