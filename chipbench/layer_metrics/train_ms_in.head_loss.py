"""Mean device time of one training step in the MLM and NSP heads, the tied
decoder's product and the loss (``loss``), forward and backward; self times
of the device events by their scope path, a chip's mean,
``device_scopes.py``."""
import device_scopes


def read(trace, counters, record):
    return device_scopes.metric(trace, "train", "head_loss")
