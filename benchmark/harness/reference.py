"""The plain reference: exact answers over the records a run sent.

Imports nothing of the program. The flow key and the service group are
the deployment's key definitions (a murmur3-finalized hash_combine of the
5-tuple, and of the service 3-tuple modulo the HLL group count); they are
written out here so that the reference can name the keys the program
serves. Everything else is counting.
"""

from __future__ import annotations

import math
from typing import Dict, Sequence

import numpy as np

_U32 = np.uint32
_GOLDEN = _U32(0x9E3779B9)


def _mix32(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> _U32(16))
    x = x * _U32(0x85EBCA6B)
    x = x ^ (x >> _U32(13))
    x = x * _U32(0xC2B2AE35)
    return x ^ (x >> _U32(16))


def fold(cols: Sequence[np.ndarray]) -> np.ndarray:
    """uint32 key of N uint32 columns: h = mix32(h ^ (c + G + h<<6 + h>>2))."""
    cols = [np.asarray(c).astype(_U32) for c in cols]
    with np.errstate(over="ignore"):
        h = np.full_like(cols[0], _GOLDEN)
        for c in cols:
            h = _mix32(h ^ (c + _GOLDEN + (h << _U32(6)) + (h >> _U32(2))))
    return h


def flow_keys(cols: Dict[str, np.ndarray]) -> np.ndarray:
    return fold([cols["ip_src"], cols["ip_dst"], cols["port_src"],
                 cols["port_dst"], cols["proto"]])


def sent_multiplicity(pool_records: int, sent: int) -> np.ndarray:
    """How often each pool record went out when `sent` records were sent
    in pool order, cyclically."""
    m = np.full(pool_records, sent // pool_records, np.int64)
    m[:sent % pool_records] += 1
    return m


class Reference:
    """Exact counts, distinct clients per service group and packet mass
    of everything sent."""

    def __init__(self, cols: Dict[str, np.ndarray], sent: int,
                 hll_groups: int) -> None:
        mult = sent_multiplicity(len(cols["ip_src"]), sent)
        keys = flow_keys(cols)
        self.keys, inverse = np.unique(keys, return_inverse=True)
        self.counts = np.bincount(inverse, weights=mult,
                                  minlength=len(self.keys)).astype(np.int64)
        self.records = int(mult.sum())
        pkts = (cols["packet_tx"].astype(np.int64)
                + cols["packet_rx"].astype(np.int64))
        self.packets = int((pkts * mult).sum())
        live = mult > 0
        group = fold([cols["ip_dst"], cols["port_dst"], cols["proto"]]) \
            % _U32(hll_groups)
        pairs = (group.astype(np.uint64) << np.uint64(32)) \
            | cols["ip_src"].astype(np.uint64)
        self.distinct_clients = int(len(np.unique(pairs[live])))
        self.order = np.argsort(-self.counts, kind="stable")

    def count(self, keys: np.ndarray) -> np.ndarray:
        i = np.searchsorted(self.keys, keys)
        i = np.minimum(i, len(self.keys) - 1)
        return np.where(self.keys[i] == keys, self.counts[i], 0)

    def heaviest(self, k: int) -> np.ndarray:
        return self.keys[self.order[:k]]

    def kth_count(self, k: int) -> int:
        return int(self.counts[self.order[min(k, len(self.order)) - 1]])

    def sample(self, k: int, seed: int) -> np.ndarray:
        """k distinct sent keys drawn from the seed."""
        rng = np.random.default_rng([seed, 0xC0DE])
        live = self.keys[self.counts > 0]
        return rng.choice(live, size=min(k, len(live)), replace=False)


def cms_bound(records: int, log2_width: int) -> float:
    """Count-Min's stated over-count: e * N / w."""
    return math.e * records / (1 << log2_width)


def hll_limit(precision: int) -> float:
    """Three standard errors of a 2^p-register HyperLogLog."""
    return 3 * 1.04 / math.sqrt(1 << precision)
