"""Mean device time of one decode step (the executable whose operations stand
under ``serve_step.decode``) in latent attention's absorbed products, a head
each: the queries carried into the latent (``q_nope W_uk``) and the attended
latent carried out to the values (``o_lat W_uv``), the op scope
``attn.absorb``. A part of ``decode_ms_in.attn_proj``, not beside it
(``latent_scopes.py``)."""
import latent_scopes


def read(trace, counters, record):
    return latent_scopes.metric(trace, "decode", "attn.absorb")
