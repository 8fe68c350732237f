"""backend.upload_ms: host time per explicit host-to-device slab upload of
the kernel backend's exact scan (``device_put`` until the transfer is
done, inside the ``rac/upload`` span), ms: the program's ``upload_s``
clock over its ``uploads`` count (``metrics_snapshot()["dispatch"]``),
across the traced window.  None where the program counts no upload."""


def read(ctx):
    a = ctx["rec"]["snap0"].get("dispatch", {})
    b = ctx["rec"]["snap1"].get("dispatch", {})
    n = b.get("uploads", 0) - a.get("uploads", 0)
    return None if n <= 0 else (b["upload_s"] - a["upload_s"]) / n * 1e3
