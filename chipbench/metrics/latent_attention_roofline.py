"""Roofline share of the Pallas latent (MLA) decode attention kernel (%).

The kernel is bound by HBM: the least time is the latent rows the
window's decode steps need -- the delta of the loop's `kv_blocks_read`
counter over the window (readings["counters"], the live blocks of every
decode step) times the bytes of one block's rows over every layer
(readings["latent_block_bytes"], chipbench/latent_flops.py: 576 bf16
values a token, not the 640 lanes they are stored in) -- over the chip's
HBM bandwidth.  The time is the device self time of the operations named
by the kernel, `paged_latent_attention` (its `pallas_call` name), in
whatever program runs them.  A run without that kernel or counter reads
nothing."""
from chipbench import peaks

KERNEL = "paged_latent_attention"


def read(readings):
    trace = readings.get("trace")
    counters = readings.get("counters")
    if not trace or not counters or not readings.get("latent_block_bytes"):
        return None
    blocks = (counters["close"].get("kv_blocks_read", 0)
              - counters["open"].get("kv_blocks_read", 0))
    secs = sum(s for k, s in trace["ops"].items()
               if k.rsplit("/", 1)[-1] == KERNEL)
    if not secs or not blocks:
        return None
    bw = peaks.of(trace["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * blocks * readings["latent_block_bytes"] / bw / secs
