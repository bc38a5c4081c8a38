"""Mean device time of one prefill chunk (the executable whose operations stand
under ``serve_step.prefill``) in the embedding's rows, the head's product,
the pick of the last position and the greedy id (``embed``, ``head``, the
head's block); self times of the device events by their scope path,
``device_scopes.py``."""
import device_scopes


def read(trace, counters, record):
    return device_scopes.metric(trace, "prefill", "head")
