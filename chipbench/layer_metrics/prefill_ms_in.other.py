"""Mean device time of one prefill chunk (the executable whose operations stand
under ``serve_step.prefill``) in what no part of this cell's list takes: the
layers' norms, residual adds, anything under no scope the reader's tables
know; self times of the device events by their scope path,
``device_scopes.py``."""
import device_scopes


def read(trace, counters, record):
    return device_scopes.metric(trace, "prefill", "other")
