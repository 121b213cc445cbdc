"""int8_reduce_roofline (%): the least time the fused int8
decompress-reduce kernel could take for the work it was given, over the
device time of its events in the trace.

Per call on one leaf of M parameters with n clients on the chip, it must
read n*M int8 values and n weights and write M float32 sums (n*M + 4*M +
4*n bytes), and do 2*n*M operations. A round calls it once per leaf and
slab; on a mesh each chip reduces its own clients. The bound is the larger
of bytes over HBM bandwidth and operations over the bf16 peak: memory, at
these sizes. Returns nothing where the trace holds no such kernel or not
the number of calls the round makes."""

KERNEL = "int8_decompress_reduce"


def read(ctx):
    t, g, c, peaks = ctx["trace"], ctx["geometry"], ctx["counters"], \
        ctx["peaks"]
    if t is None or not peaks or g.get("transport") != "int8":
        return None
    chunk = int(g.get("cohort_chunk") or g["clients_per_round"])
    slabs = -(-int(g["clients_per_round"]) // chunk)
    n = chunk / c["chips"]
    sizes = c["leaf_sizes"]
    rounds = c["traced_rounds"]
    need_bytes = sum(n * m + 4 * m + 4 * n for m in sizes) * slabs * rounds
    need_ops = sum(2 * n * m for m in sizes) * slabs * rounds
    calls = len(sizes) * slabs * rounds
    shares = []
    for dev in t["devices"].values():
        ns, count = dev["kernels"].get(KERNEL, (0.0, 0))
        if count != calls or ns <= 0:
            return None
        least = max(need_bytes / peaks["hbm_bytes_per_s"],
                    need_ops / peaks["bf16_flops_per_s"])
        shares.append(100.0 * least / (ns / 1e9))
    return sum(shares) / len(shares)
