"""backend.sync_kb_per_admit: host-to-device bytes the kernel backend's
mirrors moved (``sync_stats["bytes"]``) per admission in the traced
window, kB.  Only the fused decision pass keeps mirrors, so the metric
exists where the served path's engine drives it."""


def read(ctx):
    a, b = ctx["rec"]["snap0"], ctx["rec"]["snap1"]
    n = b["admissions"] - a["admissions"]
    if n <= 0 or "sync" not in b:
        return None
    return (b["sync"]["bytes"] - a.get("sync", {}).get("bytes", 0)) / n / 1e3
