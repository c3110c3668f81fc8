"""The one traffic generator: turns a mix's data file into requests.

A mix (`traffic/<name>.json`) fixes how load is offered:

    loop              "closed": each client waits for its reply
    clients           callers in the closed loop
    rows_per_request  result rows one request asks for
    keys              distinct moduli the requests are spread over
                      (0 for an operation that takes no modulus)
    key_zipf_s        Zipf exponent of the key popularity (0 = uniform)

The configuration's operation (`ops/<op>.py`) draws the operands.  All
of it comes from `--seed`: the same seed gives the same requests.  The
key popularity is dealt as fixed quotas in a seeded order, so that
every seed sends each key rank equally often and only the order moves.
"""

from __future__ import annotations

import json
import random
from dataclasses import dataclass
from pathlib import Path

MIX_KEYS = {"loop", "clients", "rows_per_request", "keys", "key_zipf_s"}
KEY_DEAL = 4096                 # requests per deal of the key quotas


@dataclass(frozen=True)
class Mix:
    loop: str
    clients: int
    rows_per_request: int
    keys: int
    key_zipf_s: float

    @classmethod
    def load(cls, path: Path) -> "Mix":
        raw = json.loads(Path(path).read_text())
        if set(raw) != MIX_KEYS:
            raise ValueError(f"{path}: keys {sorted(raw)}, expected "
                             f"{sorted(MIX_KEYS)}")
        mix = cls(**raw)
        if mix.loop != "closed":
            raise ValueError(f"{path}: loop {mix.loop!r} is not served; "
                             "only 'closed' is")
        if mix.clients < 1 or mix.rows_per_request < 1 or mix.keys < 0:
            raise ValueError(f"{path}: counts must be positive")
        return mix

    def request_sizes(self) -> list[int]:
        """Row totals one frontend cycle can coalesce: 1 to `clients`
        requests in flight together."""
        return [k * self.rows_per_request
                for k in range(1, self.clients + 1)]


def zipf_quotas(n_keys: int, s: float, total: int) -> list[int]:
    """Requests per key rank out of `total`, proportional to rank^-s,
    rounded by largest remainder so that they add up to `total`."""
    weights = [1.0 / (k ** s) for k in range(1, n_keys + 1)]
    norm = sum(weights)
    exact = [total * w / norm for w in weights]
    quotas = [int(x) for x in exact]
    rest = sorted(range(n_keys), key=lambda k: quotas[k] - exact[k])
    for k in rest[:total - sum(quotas)]:
        quotas[k] += 1
    return quotas


@dataclass
class Request:
    cols: tuple          # request columns, one list per operand
    v: int | None        # modulus, for operations that take one

    @property
    def rows(self) -> int:
        return len(self.cols[0])


class Stream:
    """Requests of one mix under one configuration and seed, in the
    order they are sent; `next()` never runs out."""

    def __init__(self, op, cfg: dict, mix: Mix, seed: int):
        self.rng = random.Random(seed)
        self.mix = mix
        self.op = op
        self.rows = op.Rows(cfg, self.rng)
        self.keys = op.make_keys(self.rng, cfg, mix.keys)
        if self.keys:
            quotas = zipf_quotas(len(self.keys), mix.key_zipf_s, KEY_DEAL)
            deal = [k for k, q in enumerate(quotas) for _ in range(q)]
            self.rng.shuffle(deal)
        else:
            deal = [None]
        self._deal = deal
        self._i = 0

    def next_key(self):
        k = self._deal[self._i % len(self._deal)]
        self._i += 1
        return None if k is None else self.keys[k]

    def next(self) -> Request:
        v = self.next_key()
        return Request(self.rows.take(self.mix.rows_per_request, v), v)
