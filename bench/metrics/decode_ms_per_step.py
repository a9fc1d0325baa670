"""Device time of the macro-step programs (both lanes' K-token decode
scans) per token step: one step advances every occupied row of both
lanes by one token."""
import layers


def read(run):
    t = layers.category_seconds(run, "decode")
    steps = layers.token_steps(run)
    if t is None or not steps:
        return None
    return t * 1e3 / steps
