"""Oracle families are found by file: the dense family's reference and work
count, the refusal of a family with no reference or of a section that
misnames its family, a family supplied by name reached by every caller, and
the scorer's counters in the metric context of a traced tiny run.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_oracles.py -q
"""
import dataclasses
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

from chipbench import harness, oracles, reference  # noqa: E402
from chipbench.metrics import scorer_roofline  # noqa: E402
from chipbench.oracles import dense  # noqa: E402
from test_harness import TINY_ORACLE  # noqa: E402
from test_program_metrics import traced_tiny_run  # noqa: E402


def _oracle(name):
    return json.loads((ROOT / f"chipbench/configs/{name}.json").read_text())["oracle"]


def test_load_finds_the_dense_family():
    mod = oracles.load("dense")
    assert mod is dense
    assert all(callable(getattr(mod, f)) for f in oracles.FUNCTIONS)


def test_family_without_a_reference_is_refused():
    o = dict(TINY_ORACLE, arch="olmoe-1b-7b", family="moe")
    with pytest.raises(ValueError, match="chipbench/oracles/moe.py"):
        harness.oracle_config(o)


@pytest.mark.parametrize("arch,family", [("joinml-oracle", "moe"),
                                         ("olmoe-1b-7b", "dense")])
def test_section_that_misnames_its_family_is_refused(arch, family):
    o = dict(TINY_ORACLE, arch=arch, family=family)
    with pytest.raises(ValueError, match="family"):
        harness.oracle_config(o)


def test_dense_check_refuses_what_it_cannot_represent():
    cfg = harness.oracle_config(TINY_ORACLE)
    for change in ({"tied_embeddings": False}, {"act": "gelu"}):
        with pytest.raises(ValueError, match="dense, tied, SwiGLU"):
            dense.check(dataclasses.replace(cfg, **change))


def test_family_supplied_by_name_is_used(monkeypatch):
    """A family module that exists only by name (no file of the harness
    edited) is what the configuration check, the reference and the work
    count call."""
    import repro.configs

    calls = []
    toy = types.ModuleType("chipbench.oracles.toy")
    toy.check = lambda cfg: calls.append(("check", cfg.family))
    toy.yes_no_logits = lambda o, params, toks, last, yes, no, control=False: (
        calls.append(("logits", control)) or np.full((len(last), 2), 3.0))
    toy.required_flops = lambda o, lens: calls.append(("flops",)) or 7.0
    toy.required_bytes = lambda o, lens: calls.append(("bytes",)) or 11.0
    monkeypatch.setitem(sys.modules, "chipbench.oracles.toy", toy)
    base = repro.configs.get_config("joinml-oracle")
    monkeypatch.setattr(repro.configs, "get_config",
                        lambda arch: dataclasses.replace(base, family="toy"))

    o = dict(TINY_ORACLE, family="toy")
    assert oracles.load("toy") is toy
    assert harness.oracle_config(o).family == "toy"
    got = reference.yes_no_logits(o, None, np.zeros((4, 8)), np.zeros(4), 5, 6,
                                  control=True)
    assert got.shape == (4, 2) and (got == 3.0).all()
    assert scorer_roofline.required_flops(o, [3, 4]) == 7.0
    assert scorer_roofline.required_bytes(o, [3, 4]) == 11.0
    assert calls == [("check", "toy"), ("logits", True), ("flops",),
                     ("bytes",)]


@pytest.mark.parametrize("name", ["dblp-scholar", "abt-buy"])
def test_dense_work_counts_are_exact(name):
    """The published-width oracle's counts at real lengths (57, 200, 509),
    as the harness computed them before the families moved out."""
    o = _oracle(name)
    lens = np.array([57, 200, 509])
    assert dense.dense_params(o) == 113_246_208
    assert dense.required_flops(o, lens) == 179_079_865_344.0
    assert dense.required_bytes(o, lens) == 227_672_064.0
    assert scorer_roofline.required_flops(o, lens) == 179_079_865_344.0
    assert scorer_roofline.required_bytes(o, lens) == 227_672_064.0


@pytest.mark.parametrize("dispatch", ["streaming", "dense"])
def test_scorer_counters_reach_the_metric_context(dispatch, monkeypatch):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    per_layer = [m for m in bench["per_layer"] if m["name"] == "pad_share"]
    line, seen = traced_tiny_run(dispatch, monkeypatch, per_layer, "counters")
    assert line["correct"], line["checks"]
    got = seen["ctx"].scorer_counters
    assert set(harness.SCORER_COUNTERS) <= set(got)
    tokens, slots, causal, pairs = seen["window"]
    assert slots > 0
    assert [got["tokens"], got["token_slots"], got["causal_pairs"],
            got["pairs_scored"]] == [tokens, slots, causal, pairs]
    assert 100.0 * (1.0 - got["tokens"] / got["token_slots"]) == pytest.approx(
        line["metrics"]["pad_share"]["value"], rel=1e-12)
    blocks = seen["probe"].blocks
    assert got["pairs_scored"] == sum(len(lens) for _, _, lens in blocks)
    assert got["causal_pairs"] == sum(int((lens * (lens + 1) // 2).sum())
                                      for _, _, lens in blocks)


def test_scorer_counters_prefer_the_scorers_own():
    """A scorer that offers ``counters()`` (a family's mechanism counted
    with the common counters) is read through it; one without is read by
    the common counters' names."""
    common = dict.fromkeys(harness.SCORER_COUNTERS, 3)
    own = types.SimpleNamespace(counters=lambda: dict(common, tokens_per_expert=5))
    assert harness.scorer_counters(own) == dict(common, tokens_per_expert=5)
    assert harness.scorer_counters(types.SimpleNamespace(**common)) == common
