"""sim_top1_roofline: the exact Top-1 scan kernel's share of its roofline, %.

Per call, from the shapes the trace gives it (queries f32[Q,D], store
f32[N,D], the n_valid scalar; results f32[Q,1] and s32[Q,1]):
bytes = what it reads and writes, flops = 2 Q N D.  The least time is the
larger of bytes / HBM bandwidth and flops / the bf16 matmul peak; at these
shapes the bytes bind (the store is read once per call).  The share is the
sum of those least times over the summed device time of the calls."""


def call_bytes(results, operands) -> int:
    from trace import nbytes
    return sum(nbytes(s) for s in results) + sum(nbytes(s) for s in operands)


def call_flops(results, operands) -> int:
    (_, (q, d)), (_, (n, _)) = operands[1], operands[2]
    return 2 * q * n * d


def least_s(results, operands, peaks) -> float:
    return max(call_bytes(results, operands) / peaks["hbm_bytes_per_s"],
               call_flops(results, operands) / peaks["bf16_flops"])


def read(ctx):
    calls = ctx["trace"]["kernels"].get("sim_top1", [])
    spent = sum(c[0] for c in calls)
    if not calls or spent <= 0:
        return None
    return 100.0 * sum(least_s(r, o, ctx["peaks"]) for _, r, o in calls) / spent
