"""Tiny cells for the benchmark's own tests on the CPU: the cell's schema,
traffic shape and code paths, at a few thousand rows."""
TINY_CONFIG = {"preload_rows": 2048, "load_batch_rows": 1024,
               "lsm": {"flush_rows": 1024, "fanout": 4, "pq_m": 8,
                       "quantize_vectors": True, "wal_group_records": 8,
                       "wal_group_bytes": 1 << 20}}
TINY_TRAFFIC = {
    "tracy.read-fused": {"warmup_blocks": 1,
                         "write": {"insert": 128, "update": 0, "delete": 0}},
}


def tiny(cell):
    """``run_cell`` overrides that shrink ``cell`` to a test's size."""
    return {"config": dict(TINY_CONFIG), "traffic": TINY_TRAFFIC[cell]}
