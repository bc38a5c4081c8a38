"""Mean device time of one prefill chunk (the executable whose operations
stand under ``serve_step.prefill``) in latent attention's absorbed products,
a head each (``attn.absorb``: ``q_nope W_uk`` and ``o_lat W_uv``). A part of
``prefill_ms_in.attn_proj``, not beside it (``latent_scopes.py``)."""
import latent_scopes


def read(trace, counters, record):
    return latent_scopes.metric(trace, "prefill", "attn.absorb")
