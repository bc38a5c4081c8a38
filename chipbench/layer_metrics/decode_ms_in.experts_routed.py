"""Mean device time of one decode step (the executable whose operations stand
under ``serve_step.decode``) in the routed experts: scores, top-k, the sort
and the combine (``experts.router``) and the experts' products
(``experts.routed``); self times of the device events by their scope path,
``device_scopes.py``."""
import device_scopes


def read(trace, counters, record):
    return device_scopes.metric(trace, "decode", "experts_routed")
