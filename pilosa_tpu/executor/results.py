"""Query result types.

Mirrors the reference result shapes (Row row.go:27, Pairs cache.go:305,
ValCount executor.go, GroupCount executor.go:1009) with one change: a Row
result keeps its per-shard device words until something asks for columns —
most pipelines (Count, sub-expressions) never materialize host columns.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from pilosa_tpu.ops.bitset import SHARD_WIDTH, unpack_positions
from pilosa_tpu.utils.profile import transfer


class RowResult:
    """A query-result bitmap partitioned by shard (reference Row/rowSegment,
    row.go:27,297)."""

    def __init__(self, shards: List[int], words):
        # words: device or numpy array [len(shards), WORDS_PER_SHARD]
        self.shards = list(shards)
        self.words = words
        self.attrs: Dict[str, Any] = {}
        self.keys: Optional[List[str]] = None
        self._columns: Optional[np.ndarray] = None

    # graftlint: materialize — columns() IS the device->host boundary:
    # callers ask for host column ids exactly once, and the fetch is
    # cached on the result.
    def columns(self) -> np.ndarray:
        if self._columns is not None:
            return self._columns
        # `words` may be a fusion handle (executor/fusion.FusedEval):
        # np.asarray resolves it against the fused batch output, one
        # shared transfer per fusion group. The fetch is a `d2h` stage
        # of the request record: only a response that reads columns
        # pays it (Count, excludeColumns never do).
        words = self.words
        if isinstance(words, np.ndarray):
            host = words
        else:
            with transfer("d2h", int(getattr(words, "nbytes", 0) or 0)):
                host = np.asarray(words)
        out = []
        for i, shard in enumerate(self.shards):
            pos = unpack_positions(host[i])
            if len(pos):
                out.append(pos + np.uint64(shard * SHARD_WIDTH))
        self._columns = (np.concatenate(out) if out
                         else np.empty(0, dtype=np.uint64))
        return self._columns

    def clear_columns(self) -> None:
        """Drop column data, keeping attrs (reference ExcludeColumns empties
        the row's segments, executor.go:532-534)."""
        self.words = np.zeros((len(self.shards),
                               self.words.shape[-1] if hasattr(
                                   self.words, "shape") else 0),
                              dtype=np.uint32)
        self._columns = np.empty(0, dtype=np.uint64)

    # graftlint: materialize — scalar count for response shaping; the
    # executor's fused Count path never routes through here.
    def count(self) -> int:
        from pilosa_tpu.ops.bitset import popcount
        import jax.numpy as jnp
        words = self.words
        dw = getattr(words, "device_words", None)
        if dw is not None:  # fusion handle: slice on device, no bounce
            words = dw()
        return int(np.asarray(popcount(jnp.asarray(words),
                                       axis=(-2, -1))))

    def to_json(self) -> dict:
        d = {"columns": self.columns().tolist()}
        if self.attrs:
            d["attrs"] = self.attrs
        if self.keys is not None:
            d["keys"] = self.keys
        return d


@dataclass
class PairsResult:
    """TopN result: (id, count) pairs sorted desc (reference Pairs)."""
    pairs: List[Tuple[int, int]]
    keys: Optional[List[str]] = None

    def to_json(self):
        if self.keys is not None:
            return [{"key": k, "count": int(c)}
                    for (r, c), k in zip(self.pairs, self.keys)]
        return [{"id": int(r), "count": int(c)} for r, c in self.pairs]


@dataclass
class ValCount:
    """Sum/Min/Max result (reference ValCount)."""
    value: int
    count: int

    def to_json(self):
        return {"value": int(self.value), "count": int(self.count)}


@dataclass
class RowIdentifiers:
    """Rows() result (reference RowIdentifiers)."""
    rows: List[int]
    keys: Optional[List[str]] = None

    def to_json(self):
        if self.keys is not None:
            return {"keys": self.keys}
        return {"rows": [int(r) for r in self.rows]}


@dataclass
class FieldRow:
    field: str
    row_id: int
    row_key: Optional[str] = None

    def to_json(self):
        d = {"field": self.field}
        if self.row_key is not None:
            d["rowKey"] = self.row_key
        else:
            d["rowID"] = int(self.row_id)
        return d


@dataclass
class GroupCount:
    """One GroupBy group (reference GroupCount, executor.go:1009)."""
    group: List[FieldRow]
    count: int
    # GroupBy(..., aggregate=Sum(field=f)): the signed sum of f over the
    # group's columns that have a value; None (and no JSON key) without
    # the argument.
    sum: Optional[int] = None

    def to_json(self):
        out = {"group": [g.to_json() for g in self.group],
               "count": int(self.count)}
        if self.sum is not None:
            out["sum"] = int(self.sum)
        return out


def result_to_json(result) -> Any:
    if hasattr(result, "to_json"):
        return result.to_json()
    if isinstance(result, list):
        return [result_to_json(r) for r in result]
    if isinstance(result, (bool, int, str, type(None))):
        return result
    if isinstance(result, np.integer):
        return int(result)
    raise TypeError(f"unserializable result {type(result)}")
