"""Mean device time of one prefill chunk (the executable whose operations
stand under ``serve_step.prefill``) in the gated delta rule of the linear
layers, in chunks of 64 positions (``delta.rule``). A part of
``prefill_ms_in.ssm``, not beside it (``latent_scopes.py``)."""
import latent_scopes


def read(trace, counters, record):
    return latent_scopes.metric(trace, "prefill", "delta.rule")
