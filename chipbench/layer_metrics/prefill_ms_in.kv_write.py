"""Mean device time of one prefill chunk (the executable whose operations stand
under ``serve_step.prefill``) in the new K/V rows written into their pages
or ring (``kv.write``); self times of the device events by their scope path,
``device_scopes.py``."""
import device_scopes


def read(trace, counters, record):
    return device_scopes.metric(trace, "prefill", "kv_write")
