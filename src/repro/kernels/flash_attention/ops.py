"""Public op: fused GQA flash attention (interpret on CPU, compiled on TPU)."""
from .. import interpret_mode
from .kernel import flash_attention_pallas
from .ref import flash_attention_ref  # noqa: F401


def flash_attention(q, k, v, causal=True, window=0, bq=128, bkv=128):
    bq = min(bq, q.shape[2])
    bkv = min(bkv, k.shape[2])
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, bq=bq, bkv=bkv,
        interpret=interpret_mode(),
    )
