from repro.kernels.decode_attention.ops import (
    decode_attention_cache, paged_decode_attention,
    paged_latent_decode_attention, paged_latent_pool_view)
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                paged_decode_attention_ref,
                                                paged_latent_decode_ref)
