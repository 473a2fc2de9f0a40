"""A model family for the tests: the dense reference under a name of its
own, whose ``FIELDS`` map one more key into a nested config of the
program (``ssm.chunk``, which a dense model never reads).  It records
which of its functions the harness called."""
from bench.reference import dense_llama

FIELDS = dict(dense_llama.FIELDS, ssm_chunk="ssm.chunk")
calls = []


def _recorded(name):
    real = getattr(dense_llama, name)

    def f(*a, **kw):
        calls.append(name)
        return real(*a, **kw)
    return f


init_params = _recorded("init_params")
train_steps = _recorded("train_steps")
served_token_gaps = _recorded("served_token_gaps")
train_flops_per_token = _recorded("train_flops_per_token")
matmul_params = _recorded("matmul_params")
cached_values_per_token = _recorded("cached_values_per_token")
attn_flops_per_cached_token = _recorded("attn_flops_per_cached_token")
