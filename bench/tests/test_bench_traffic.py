"""The traffic generator: seeded, the paper's operand distribution for
division, and Zipf keys over the whole key set for modexp."""

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import gen  # noqa: E402
import harness  # noqa: E402


# mixes of the cells kept for later (PERF.md, Open questions), built
# here: the generator serves them, no cell runs them yet
MIXES = {"modexp2048-manykeys": ("rsa2048-modexp",
                                 gen.Mix("closed", 16, 1, 256, 1.0))}


def stream(cell_name, seed):
    """The stream of a cell, or of a mix kept for a later cell."""
    if cell_name in MIXES:
        config, mix = MIXES[cell_name]
        cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
        cell = harness.Cell(
            workload={}, cfg=cfg, mix=mix,
            op=harness.load_module(BENCH / "ops" / f"{cfg['op']}.py"),
            end_to_end=[], per_layer=[])
    else:
        cell = harness.Cell.load(cell_name)
    return gen.Stream(cell.op, cell.cfg, cell.mix, seed), cell


def requests(cell_name, seed, n):
    s, _ = stream(cell_name, seed)
    out = []
    for _ in range(n):
        r = s.next()
        out.append((r.cols, r.v))
    return out


def test_same_seed_same_traffic_other_seed_other_traffic():
    for name in ("div2p15-single", "modexp2048-manykeys"):
        a = requests(name, 2 ** 33 + 5, 6)
        assert a == requests(name, 2 ** 33 + 5, 6)
        assert a != requests(name, 2 ** 33 + 6, 6)


def test_division_operands_follow_the_papers_distribution():
    s, cell = stream("div2p15-batch", 11)
    m = cell.cfg["m_limbs"]
    n_prec = m // 2 - 1                    # prec(v) in [2, M/2]
    us, vs = [], []
    while len(vs) < n_prec:
        r = s.next()
        assert r.rows == cell.mix.rows_per_request
        us += r.cols[0]
        vs += r.cols[1]
    limbs = lambda x: (x.bit_length() + 15) // 16  # noqa: E731
    assert {limbs(u) for u in us} == {m - 2}
    # every divisor precision in [2, M/2] once per deal: uniform, and
    # the same sizes for every seed
    assert sorted(limbs(v) for v in vs[:n_prec]) == list(range(2, m // 2 + 1))


def test_zipf_keys_span_the_key_set():
    s, cell = stream("modexp2048-manykeys", 5)
    assert len(s.keys) == cell.mix.keys == 256
    assert len(set(s.keys)) == 256
    bits = 16 * cell.cfg["m_limbs"]
    assert all(k.bit_length() == bits and k % 2 == 1 for k in s.keys)
    quotas = gen.zipf_quotas(256, 1.0, gen.KEY_DEAL)
    assert sum(quotas) == gen.KEY_DEAL
    assert min(quotas) >= 1
    assert quotas == sorted(quotas, reverse=True)
    seen = {s.next_key() for _ in range(gen.KEY_DEAL)}
    assert seen == set(s.keys)
    # rank 1 holds 1 / H(256) of the requests
    assert abs(quotas[0] / gen.KEY_DEAL - 1 / sum(1 / k for k in range(1, 257))) < 1e-3


def test_one_key_mix_and_modexp_operands():
    s, cell = stream("modexp2048-onekey", 3)
    assert len(s.keys) == 1
    for _ in range(20):
        r = s.next()
        assert r.v == s.keys[0] and r.rows == 1
        a, e = r.cols
        assert 0 <= a[0] < r.v and 0 <= e[0] < 2 ** 2048


def test_reachable_buckets_follow_the_mix():
    class Svc:
        def __init__(self, buckets):
            import repro.serving.batching as BT
            self.batcher = BT.Batcher(buckets)
    sizes = {}
    for name in ("div2p15-batch", "div2p15-single", "modexp2048-onekey",
                 "modexp2048-manykeys"):
        _, cell = stream(name, 0)
        b = cell.cfg["service"]["kwargs"]["batch_buckets"]
        sizes[name] = harness.reachable_buckets(Svc(b), cell.mix)
    assert sizes == {"div2p15-batch": [128], "div2p15-single": [8],
                     "modexp2048-onekey": [8, 64],
                     "modexp2048-manykeys": [8, 64]}


def test_mix_files_have_exactly_the_generator_keys():
    for f in (BENCH / "traffic").glob("*.json"):
        assert set(json.loads(f.read_text())) == gen.MIX_KEYS
        gen.Mix.load(f)
