"""Scheduler and cache: bytes of recurrent state held (the lanes' running
state and the snapshots in the trie's pages) over bytes of key and value
pages in use, from the pool's counters after the window."""


def read(run):
    kv = run.counters.get("kv_page_bytes_in_use")
    lanes = run.counters.get("state_bytes_lanes")
    if not kv or lanes is None:
        return None
    return (lanes + run.counters.get("state_bytes_snapshots", 0)) / kv
