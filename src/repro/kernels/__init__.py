# Pallas TPU kernels for the framework's compute hot-spots.
# Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
# wrapper with interpret fallback), ref.py (pure-jnp oracle).
#
#   fed_agg         -- K-way weighted model aggregation (the FLight exchange)
#   quant8          -- per-block int8 quantise/dequantise (compression)
#   flash_attention -- causal/windowed GQA flash attention (prefill hot-spot)
#   paged_attention -- decode attention over the paged KV pool's live blocks
#   linrec          -- blocked diagonal linear recurrence (mamba / RG-LRU)
