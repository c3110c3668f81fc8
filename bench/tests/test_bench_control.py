"""`correct` decides right: on the CPU at a tiny size (the harness's
look for a chip skipped), a sound run reads correct, and the control and
each fault the cells can have, planted under the timed path, read not
correct.  The configurations in data/ are the cells' own at 8-limb
division and 64-bit modexp operands."""

import json
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402

TINY = {"table1-div-2p15": "bench/tests/data/tiny-div.json",
        "rsa2048-modexp": "bench/tests/data/tiny-modexp.json"}


@pytest.fixture(scope="module")
def spec_path(tmp_path_factory):
    """BENCHMARK.json with each configuration's file swapped for its
    tiny copy; cells, mixes and metrics as they are."""
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        c["file"] = TINY[c["name"]]
    path = tmp_path_factory.mktemp("spec") / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return path


def run(spec_path, cell, seed=3, seconds=1.0, **kw):
    return harness.run(cell, seed, seconds, False, t_start=time.perf_counter(),
                       need_chip=False, spec_path=spec_path, **kw)


class Broken:
    """The division or modexp service with one fault planted where
    answers are produced."""

    def __init__(self, service, fault):
        self._service = service
        self.fault = fault

    def __getattr__(self, name):
        return getattr(self._service, name)

    def divide(self, us, vs, **kw):
        if self.fault == "unchanged":          # the step hands back its input
            return list(us), list(vs)
        if self.fault == "half_batch":         # half the rows never computed
            keep = len(us) // 2
            qs, rs = self._service.divide(us[:keep], vs[:keep], **kw) \
                if keep else ([], [])
            return qs + [0] * (len(us) - keep), rs + [0] * (len(us) - keep)
        qs, rs = self._service.divide(us, vs, **kw)
        qs[0] ^= 1                             # one answer altered
        return qs, rs

    def modexp(self, a, e, v, **kw):
        if self.fault == "unchanged":
            return list(a)
        if self.fault == "half_batch":
            keep = len(a) // 2
            out = self._service.modexp(a[:keep], e[:keep], v, **kw) \
                if keep else []
            return out + [0] * (len(a) - keep)
        out = self._service.modexp(a, e, v, **kw)
        out[0] ^= 1
        return out


def test_a_sound_run_is_correct(spec_path):
    out = run(spec_path, "div2p15-batch")
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == {"ops_per_s", "latency_p50_ms",
                                   "latency_p95_ms", "setup_s"}


@pytest.mark.parametrize("cell", ["div2p15-batch", "modexp2048-onekey"])
@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_a_planted_fault_is_not_correct(spec_path, cell, fault):
    out = run(spec_path, cell, wrap=lambda s: Broken(s, fault))
    assert not out["correct"]
    assert out["compared"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("cell", ["div2p15-batch", "modexp2048-onekey"])
def test_the_control_is_not_correct(spec_path, cell):
    out = run(spec_path, cell, control=True, seconds=1.5)
    assert not out["correct"]
    assert out["compared"]["wrong_answers"]["value"] > 0
