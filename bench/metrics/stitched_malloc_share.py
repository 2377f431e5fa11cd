"""Window mallocs that handed out a stitched block (an sBlock) over all the
window's mallocs of a chunk or more, in % (the GMLake allocator's
``stitched_mallocs`` and ``state_counts`` S1-S5, read before and after the
window). None where the run recorded no such counts."""


def read(rec):
    win = rec.get("alloc_window")
    if not win:
        return None
    before, after = win["before"], win["after"]
    if before["state_counts"] is None or before["stitched_mallocs"] is None:
        return None
    mallocs = sum(after["state_counts"].values()) - sum(before["state_counts"].values())
    if not mallocs:
        return None
    return 100.0 * (after["stitched_mallocs"] - before["stitched_mallocs"]) / mallocs
