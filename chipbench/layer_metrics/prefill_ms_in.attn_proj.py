"""Mean device time of one prefill chunk (the executable whose operations stand
under ``serve_step.prefill``) in the attention block outside its core: the
q, k, v and o products and the rotation (``attn.rope``); self times of the
device events by their scope path, ``device_scopes.py``."""
import device_scopes


def read(trace, counters, record):
    return device_scopes.metric(trace, "prefill", "attn_proj")
