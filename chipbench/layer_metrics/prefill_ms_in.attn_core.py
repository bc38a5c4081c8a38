"""Mean device time of one prefill chunk (the executable whose operations stand
under ``serve_step.prefill``) in attention itself: the Pallas decode kernel
(``attn.kernel``), or in XLA the scores, softmax and values
(``attn.scores``) with the pages gathered for them (``kv.gather``); self
times of the device events by their scope path, ``device_scopes.py``."""
import device_scopes


def read(trace, counters, record):
    return device_scopes.metric(trace, "prefill", "attn_core")
