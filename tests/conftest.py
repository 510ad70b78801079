"""Shared fixtures. NOTE: do NOT set xla_force_host_platform_device_count
here — smoke tests and benches must see 1 device (the dry-run sets its own
flags in its own process)."""
import numpy as np
import pytest

from repro.core.lsm import LSMConfig, LSMStore
from repro.core.types import Column, ColumnType, IndexKind, Schema

WORDS = ["apple", "banana", "cherry", "delta", "echo", "foxtrot",
         "golf", "hotel"]


def tweet_schema(dim: int = 16) -> Schema:
    return Schema([
        Column("embedding", ColumnType.VECTOR, dim=dim, index=IndexKind.IVF),
        Column("coordinate", ColumnType.SPATIAL, index=IndexKind.ZORDER),
        Column("content", ColumnType.TEXT, index=IndexKind.INVERTED),
        Column("time", ColumnType.SCALAR, index=IndexKind.BTREE),
    ])


def make_batch(rng, n, dim=16, pk_start=0):
    return list(range(pk_start, pk_start + n)), {
        "embedding": rng.normal(size=(n, dim)).astype(np.float32),
        "coordinate": rng.uniform(0, 10, (n, 2)).astype(np.float32),
        "content": np.asarray(
            [" ".join(rng.choice(WORDS, 3)) for _ in range(n)], object),
        "time": rng.uniform(0, 100, n),
    }


@pytest.fixture(scope="module")
def small_store():
    rng = np.random.default_rng(7)
    store = LSMStore(tweet_schema(), LSMConfig(flush_rows=512))
    data = {"embedding": [], "coordinate": [], "content": [], "time": []}
    for i in range(0, 3000, 500):
        pks, batch = make_batch(rng, 500, pk_start=i)
        store.put(pks, batch)
        for k in data:
            data[k].append(batch[k])
    store.flush()
    ref = {k: np.concatenate(v) for k, v in data.items()}
    return store, ref


def packed_column(x, pks):
    """A ``PackedColumn`` over bare arrays, as one segment: the operand
    ``kernels.ops.fused_scan_topk`` takes."""
    from repro.core.segment import PackedColumn
    n = len(x)
    return PackedColumn(x=np.asarray(x, np.float32),
                        pks=np.asarray(pks, np.int64),
                        sids=np.zeros(n, np.int64),
                        rows=np.arange(n, dtype=np.int64),
                        offsets=np.asarray([0, n], np.int64))
