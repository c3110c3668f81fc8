"""`correct` decides right: on the CPU at a tiny size (the harness's
look for a chip skipped), a sound run reads correct, and the control and
each fault the cells can have, planted under the timed path, read not
correct.  Each configuration's tiny copy is found by name in data/
(`tiny-<config>.json`): the configuration's own keys at 8-limb division
and 64-bit modexp operands.  Every cell of BENCHMARK.json whose
configuration has one is covered, so a configuration enters with new
files alone."""

import copy
import json
import shutil
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402

TINY_DIR = BENCH / "tests" / "data"
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def tiny_copy(config: str, tiny_dir: Path = TINY_DIR) -> Path:
    return tiny_dir / f"tiny-{config}.json"


def tiny_spec(spec: dict, out_dir: Path, tiny_dir: Path = TINY_DIR) -> Path:
    """`spec` written to `out_dir` with each configuration's file
    swapped for its tiny copy in `tiny_dir`; cells, mixes and metrics as
    they are.  A configuration without a tiny copy is left out with its
    cells (`test_every_config_has_a_tiny_copy` names it)."""
    spec = copy.deepcopy(spec)
    tiny = {c["name"]: tiny_copy(c["name"], tiny_dir)
            for c in spec["configs"]}
    spec["configs"] = [dict(c, file=str(tiny[c["name"]]))
                       for c in spec["configs"] if tiny[c["name"]].exists()]
    have = {c["name"] for c in spec["configs"]}
    spec["workloads"] = [w for w in spec["workloads"]
                         if w["config"] in have]
    path = out_dir / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return path


CONFIGS = [c for c in SPEC["configs"] if tiny_copy(c["name"]).exists()]
CELLS = [w["name"] for w in SPEC["workloads"]
         if tiny_copy(w["config"]).exists()]


@pytest.fixture(scope="module")
def spec_path(tmp_path_factory):
    return tiny_spec(SPEC, tmp_path_factory.mktemp("spec"))


def run(spec_path, cell, seed=3, seconds=1.0, **kw):
    return harness.run(cell, seed, seconds, False, t_start=time.perf_counter(),
                       need_chip=False, spec_path=spec_path, **kw)


class Broken:
    """The service with one fault planted where its endpoint `method`
    produces answers.  The endpoint's result is a tuple of result
    columns or one column as a list; the broken one returns the same
    shape."""

    def __init__(self, service, method, fault):
        self._service = service
        real = getattr(service, method)

        def broken(*args, **kw):
            ins = [a for a in args if isinstance(a, list)]
            n = len(ins[0])
            keep = n // 2
            if fault == "half_batch":          # half the rows never computed
                args = [a[:max(keep, 1)] if isinstance(a, list) else a
                        for a in args]
            out = real(*args, **kw)
            cols = [list(c) for c in out] if isinstance(out, tuple) \
                else [list(out)]
            if fault == "unchanged":           # the step hands back its input
                cols = [list(c) for c in ins[:len(cols)]]
            elif fault == "half_batch":
                cols = [c[:keep] + [0] * (n - keep) for c in cols]
            else:                              # one answer altered
                cols[0][0] ^= 1
            return tuple(cols) if isinstance(out, tuple) else cols[0]
        setattr(self, method, broken)

    def __getattr__(self, name):
        return getattr(self._service, name)


def test_every_config_has_a_tiny_copy():
    missing = [str(tiny_copy(c["name"]).relative_to(BENCH.parent))
               for c in SPEC["configs"]
               if not tiny_copy(c["name"]).exists()]
    assert not missing, ("add a tiny copy of each configuration, its keys "
                         f"at a size a CPU test run holds: {missing}")


@pytest.mark.parametrize("config", CONFIGS, ids=lambda c: c["name"])
def test_a_tiny_copy_keeps_to_its_configuration(config):
    full = json.loads((BENCH.parent / config["file"]).read_text())
    tiny = json.loads(tiny_copy(config["name"]).read_text())
    assert tiny["name"] == full["name"] == config["name"]
    assert tiny["op"] == full["op"]
    assert set(tiny) - {"why_tiny"} == set(full)
    assert tiny["service"]["class"] == full["service"]["class"]
    # its text is the configuration's: every value but a size, the
    # kernel path (the CPU runs `blocked`) and the service's arguments
    def text(cfg):
        return {k: v for k, v in cfg.items()
                if not isinstance(v, (int, float))
                and k not in {"kernel_impl", "service", "why_tiny"}}
    assert text(tiny) == text(full)


def test_a_sound_run_is_correct(spec_path):
    out = run(spec_path, "div2p15-batch")
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == {"ops_per_s", "latency_p50_ms",
                                   "latency_p95_ms", "setup_s"}


def test_a_split_metric_reads_its_quantity(spec_path):
    out = run(spec_path, "div2p15-single")
    assert out["correct"], out["compared"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(m) == {"ops_per_s", "latency_p50_ms.single",
                      "latency_p95_ms.single", "setup_s"}
    assert 0 < m["latency_p50_ms.single"] <= m["latency_p95_ms.single"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_a_planted_fault_is_not_correct(spec_path, cell, fault):
    from repro.serving.frontend import _OPS
    method = _OPS[harness.Cell.load(cell, spec_path).op.OP][0]
    out = run(spec_path, cell, wrap=lambda s: Broken(s, method, fault))
    assert not out["correct"]
    assert out["compared"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(spec_path, cell):
    out = run(spec_path, cell, control=True, seconds=1.5)
    assert not out["correct"]
    assert out["compared"]["wrong_answers"]["value"] > 0


def test_a_config_added_as_new_files_only_enters(tmp_path):
    """A configuration, its tiny copy and a cell, with no edit to any
    file of the benchmark: the tests' spec takes it and a sound run of
    its cell reads correct."""
    tiny_dir = tmp_path / "data"
    shutil.copytree(TINY_DIR, tiny_dir)
    base = json.loads(tiny_copy("rsa2048-modexp").read_text())
    name = "rsa2048-modexp-copy"
    tiny_copy(name, tiny_dir).write_text(json.dumps(dict(base, name=name)))
    spec = copy.deepcopy(SPEC)
    conf = next(c for c in spec["configs"] if c["name"] == "rsa2048-modexp")
    spec["configs"].append(dict(conf, name=name,
                                file="bench/configs/rsa2048-modexp-copy.json"))
    cell = next(w for w in spec["workloads"] if w["config"] == conf["name"])
    spec["workloads"].append(dict(cell, name="modexp-copy-onekey",
                                  config=name))
    path = tiny_spec(spec, tmp_path, tiny_dir)
    built = json.loads(path.read_text())
    assert {c["name"] for c in built["configs"]} == \
        {c["name"] for c in spec["configs"]}
    assert "modexp-copy-onekey" in {w["name"] for w in built["workloads"]}
    out = run(path, "modexp-copy-onekey")
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
