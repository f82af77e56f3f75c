from repro.kernels.paged_attention.ops import (paged_decode_attention,
                                              paged_latent_attention)
