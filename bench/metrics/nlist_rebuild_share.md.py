"""Neighbor-list rebuilds over updates in the window, percent: the
program's ``BatchedMD.stats()`` counters ``nlist_rebuilds`` and
``nlist_updates``, read before and after the window."""


def read(r):
    counts = r.get("nlist.md") or {}
    updates = counts.get("nlist_updates")
    return 100.0 * counts["nlist_rebuilds"] / updates if updates else None
