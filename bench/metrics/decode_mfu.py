"""Model FLOPs of the tokens the traced window decoded (each token
through the SLM, and through the LLM too on the cloud lane, at its own
context length; ``flops.token_flops``) over the device time of the
macro-step programs times chips times the bf16 peak."""
import layers


def read(run):
    return layers.decode_mfu(run)
