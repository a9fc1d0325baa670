"""Device time of the prefill programs (packed, prefix, chunk and suffix
prefill of both models) per 1 000 prompt tokens they prefilled in the
traced window."""
import layers


def read(run):
    t = layers.category_seconds(run, "prefill")
    tok = layers.prefill_tokens(run)
    if t is None or not tok:
        return None
    return t * 1e3 / (tok / 1000.0)
