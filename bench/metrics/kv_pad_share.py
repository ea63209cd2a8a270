"""kv_pad_share (kv store): the share of the KV pool's device bytes that
is padding, in percent: 100 x (device - element bytes) / device bytes of
the store's page arrays, from the two counters the store puts on the
engine's registry once, at construction (program counters).  A program
whose store counts nothing reads nothing."""


def read(run):
    counts = run.samples.counters() if run.samples is not None else {}
    device = counts.get("kv_pool_device_bytes", 0)
    if not device:
        return None
    return 100.0 * (device - counts["kv_pool_element_bytes"]) / device
