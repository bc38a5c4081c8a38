"""Mean device time of one training step in the rest: the embeddings, the casts
of the parameters, every collective; self times of the device events by
their scope path, a chip's mean, ``device_scopes.py``."""
import device_scopes


def read(trace, counters, record):
    return device_scopes.metric(trace, "train", "other")
