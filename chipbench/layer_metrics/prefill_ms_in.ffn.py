"""Mean device time of one prefill chunk (the executable whose operations stand
under ``serve_step.prefill``) in the dense feed-forward block; self times of
the device events by their scope path, ``device_scopes.py``."""
import device_scopes


def read(trace, counters, record):
    return device_scopes.metric(trace, "prefill", "ffn")
