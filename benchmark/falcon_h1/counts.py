"""Bytes and FLOPs of a decoder whose every layer runs an SSD (Mamba-2) mixer
and grouped-query attention side by side: the ``counts`` module of
``falcon-h1-34b`` (README, "A layer equation"), each function for ONE chip.

A layer holds q k v o, the mixer's packed input projection (``hidden -> d_ssm
+ d_ssm + 2 G N``, Q40) with its ``dt`` rows (``H x hidden``, float32), the
mixer's output projection and three feed-forward planes. EVERY layer holds a
K/V cache, and per sequence a float32 state ``H x P x N`` that a decode step
reads once and writes once, and the convolution's last ``K - 1`` inputs.

The chunk form's FLOPs are the chunkwise algorithm's own at sub-chunks of
``mamba_chunk_size`` (README's rule: what the work NEEDS): a group's ``C B^T``
once for its heads, per head ``(C B^T * decay) (dt x)``, and per sub-chunk the
two products with the state, ``C S`` and ``(dt x)^T B``.
"""

STEP_KERNEL = "ssd_step"


def _dims(model: dict):
    d, h, L = model["hidden_size"], model["intermediate_size"], model["num_hidden_layers"]
    q = model["head_dim"] * model["num_attention_heads"]
    kv = model["head_dim"] * model["num_key_value_heads"]
    return d, h, L, q, kv, model["vocab_size"]


def _mixer(model: dict):
    """``(heads, head width, groups, state size, mixer width, conv channels, taps)``."""
    H, P, G, N = model["mamba_n_heads"], model["mamba_d_head"], model["mamba_n_groups"], model["mamba_d_state"]
    return H, P, G, N, H * P, H * P + 2 * G * N, model["mamba_d_conv"]


def layer_matmul_weights(model: dict) -> int:
    """Weights in the Q40 planes of all layers."""
    d, h, L, q, kv, _v = _dims(model)
    _H, _P, _G, _N, d_ssm, conv, _K = _mixer(model)
    return L * (d * q + 2 * d * kv + q * d + d * (d_ssm + conv) + d_ssm * d + 3 * d * h)


def _small_bytes(model: dict) -> int:
    """The float32 leaves a dispatch reads: the dt rows, taps and their bias."""
    d, _h, L, *_ = _dims(model)
    H, _P, _G, _N, _d_ssm, conv, K = _mixer(model)
    return L * (H * d + (K + 1) * conv) * 4


def state_bytes(model: dict, rows: float, tail_bytes: int = 2) -> float:
    """One read and one write of ``rows`` sequences' recurrent state and
    convolution tails, all layers."""
    L = model["num_hidden_layers"]
    H, P, _G, N, _d_ssm, conv, K = _mixer(model)
    return 2.0 * rows * L * (H * P * N * 4 + (K - 1) * conv * tail_bytes)


def _weight_bytes(model: dict, scale_bytes: int) -> float:
    return layer_matmul_weights(model) * (1.0 + scale_bytes / 32.0) + _small_bytes(model)


def decode_step_bytes(model: dict, *, rows, context_tokens, chips: int = 1,
                      kv_bytes: int = 2, scale_bytes: int = 2, head_bytes: int = 2) -> float:
    """Every plane once as it is held, the dense head, the K and V rows of
    every cached position in every layer, every row's state read and
    written, the embedding rows."""
    d, _h, L, _q, kv, v = _dims(model)
    cache = 2 * L * kv * kv_bytes * context_tokens
    return ((_weight_bytes(model, scale_bytes) + v * d * head_bytes + cache + state_bytes(model, rows)) / chips
            + rows * d * 2)


def _step_flops_per_row(model: dict) -> float:
    """The step form for one row: decay, the outer product's add, ``S C``."""
    H, P, _G, N, *_ = _mixer(model)
    return 5.0 * model["num_hidden_layers"] * H * P * N


def decode_step_flops(model: dict, *, rows, context_tokens, chips: int = 1) -> float:
    d, _h, L, q, _kv, v = _dims(model)
    H = model["mamba_n_heads"]
    return (rows * (2.0 * (layer_matmul_weights(model) + L * H * d + v * d) + _step_flops_per_row(model))
            + 4.0 * L * q * context_tokens) / chips


def chunk_form_flops(model: dict, chunk: int) -> float:
    """The chunkwise recurrence over ``chunk`` tokens, all layers."""
    H, P, G, N, *_ = _mixer(model)
    C = min(model["mamba_chunk_size"], chunk)
    per_sub = (G * 2.0 * C * C * N                  # a group's C B^T
               + H * (C * C + 2.0 * C * C * P)      # the decay's product, then (..) (dt x)
               + H * 4.0 * C * P * N)               # C S and (dt x)^T B
    return model["num_hidden_layers"] * (chunk / C) * per_sub


def prefill_chunk_flops(model: dict, *, chunk, context_before, chips: int = 1) -> float:
    """The planes' matmuls and the dt rows, causal attention over what each
    token may see, the chunk form; no head."""
    d, _h, L, q, _kv, _v = _dims(model)
    attended = chunk * context_before + chunk * (chunk + 1) / 2.0
    return (2.0 * chunk * (layer_matmul_weights(model) + L * model["mamba_n_heads"] * d)
            + 4.0 * L * q * attended + chunk_form_flops(model, chunk)) / chips


def prefill_chunk_bytes(model: dict, *, chunk, context_before, chips: int = 1,
                        kv_bytes: int = 2, scale_bytes: int = 2) -> float:
    _d, _h, L, _q, kv, _v = _dims(model)
    cache = 2 * L * kv * kv_bytes * (context_before + chunk)
    return (_weight_bytes(model, scale_bytes) + cache + state_bytes(model, 1)) / chips


def kernel_counts(model: dict, kernel: str, *, rows) -> dict | None:
    """Bytes and FLOPs of ONE call of a named kernel over ``rows`` rows, and
    how many calls one run of its program makes; None for a kernel this
    configuration does not have. ``ssd_step``: one layer's step form, each
    row's state read once and written once; ``dt x`` and the decay in (two
    float32 columns a head), a group's B and C in, ``y`` out."""
    if kernel != STEP_KERNEL:
        return None
    H, P, G, N, *_ = _mixer(model)
    vectors = (3 * H * P + 2 * G * N) * 4
    return {"bytes": rows * (2.0 * H * P * N * 4 + vectors), "flops": 5.0 * rows * H * P * N,
            "calls_per_program": model["num_hidden_layers"]}
