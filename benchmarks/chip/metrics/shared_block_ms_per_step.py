"""Device time per decode step of Zamba2's shared blocks, in ms: the
device's self time under the model scopes ``attn`` and ``ffn`` and below
them, in the reduction of the traced span that the Zamba2 driver keeps
(``rec["spans"]``, ``spans.reduce``): the attention over [x, embedding]
with its KV cache write and read, the MLP with its LoRA, and the
projection into the Mamba layer's input, in every invocation."""

SCOPES = ("attn", "ffn")


def read(rec):
    red = rec.get("spans")
    if not red or not red["scoped"]:
        return None
    ms = [v for k, v in red["device_ms_per_step"].items()
          if k.split("/")[0] in SCOPES]
    return float(sum(ms)) if ms else None
