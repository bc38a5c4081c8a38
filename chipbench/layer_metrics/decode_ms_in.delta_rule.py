"""Mean device time of one decode step (the executable whose operations
stand under ``serve_step.decode``) in the gated delta rule of the linear
layers, their state read and written in place (the op scope
``delta.rule``). A part of ``decode_ms_in.ssm``, not beside it
(``latent_scopes.py``)."""
import latent_scopes


def read(trace, counters, record):
    return latent_scopes.metric(trace, "decode", "delta.rule")
