"""Bytes and FLOPs of a hybrid decoder (gated delta-rule layers and full
attention layers in a periodic pattern): the ``counts`` module of
``olmo-hybrid-7b`` (README, "A layer equation"), each function for ONE chip.

A linear layer holds the mixer's packed input projection (``hidden -> 2 H dk +
2 H dv``), its output projection (``H dv -> hidden``) and three feed-forward
planes; a full layer q k v o and the same three. Only the full layers hold a
K/V cache. A linear layer holds, per sequence, a float32 state ``H x dk x dv``
that a decode step reads once and writes once, and the convolution's last
``K - 1`` inputs.

The chunk form's FLOPs are the chunkwise algorithm's own at sub-chunks of 64
(README's rule: what the work NEEDS): the two ``C x C`` products ``K K^T`` and
``Q K^T``, the unit-triangular solve for ``U`` and ``W`` by substitution, and per
sub-chunk the three products with the state and ``tril(QK^T) U``. The program
inverts the triangle explicitly by repeated squaring, which costs more; it is
charged its time and not credited with that work.
"""

SUB_CHUNK = 64
STEP_KERNEL = "gated_delta_step"


def _dims(model: dict):
    d, h, L = model["hidden_size"], model["intermediate_size"], model["num_hidden_layers"]
    q = model["head_dim"] * model["num_attention_heads"]
    kv = model["head_dim"] * model["num_key_value_heads"]
    n_full = sum(t == "full_attention" for t in model["layer_types"])
    return d, h, L - n_full, n_full, q, kv, model["vocab_size"]


def _mixer(model: dict):
    H, dk, dv = model["linear_num_value_heads"], model["linear_key_head_dim"], model["linear_value_head_dim"]
    return H, dk, dv, H * (2 * dk + dv), model["linear_conv_kernel_dim"]


def layer_matmul_weights(model: dict) -> int:
    """Weights in the Q40 planes of all layers."""
    d, h, n_lin, n_full, q, kv, _v = _dims(model)
    H, _dk, dv, conv, _K = _mixer(model)
    linear = d * (conv + H * dv) + H * dv * d + 3 * d * h
    full = d * q + 2 * d * kv + q * d + 3 * d * h
    return n_lin * linear + n_full * full


def _small_bytes(model: dict) -> int:
    """The linear layers' float32 leaves a dispatch reads: gate rows, taps."""
    d, _h, n_lin, *_ = _dims(model)
    H, _dk, _dv, conv, K = _mixer(model)
    return n_lin * (2 * H * d + K * conv) * 4


def state_bytes(model: dict, rows: float, tail_bytes: int = 2) -> float:
    """One read and one write of ``rows`` sequences' recurrent state and
    convolution tails, all linear layers."""
    _d, _h, n_lin, *_ = _dims(model)
    H, dk, dv, conv, K = _mixer(model)
    return 2.0 * rows * n_lin * (H * dk * dv * 4 + (K - 1) * conv * tail_bytes)


def _weight_bytes(model: dict, scale_bytes: int) -> float:
    return layer_matmul_weights(model) * (1.0 + scale_bytes / 32.0) + _small_bytes(model)


def decode_step_bytes(model: dict, *, rows, context_tokens, chips: int = 1,
                      kv_bytes: int = 2, scale_bytes: int = 2, head_bytes: int = 2) -> float:
    """Every plane once as it is held, the dense head, the K and V rows of
    every cached position in the FULL layers, every row's state read and
    written, the embedding rows."""
    d, _h, _n_lin, n_full, _q, kv, v = _dims(model)
    cache = 2 * n_full * kv * kv_bytes * context_tokens
    return ((_weight_bytes(model, scale_bytes) + v * d * head_bytes + cache + state_bytes(model, rows)) / chips
            + rows * d * 2)


def _step_flops_per_row(model: dict) -> float:
    """The step form for one row: decay, ``S^T k``, the rank-one update, ``S^T q``."""
    _d, _h, n_lin, *_ = _dims(model)
    H, dk, dv, _conv, _K = _mixer(model)
    return 7.0 * n_lin * H * dk * dv


def decode_step_flops(model: dict, *, rows, context_tokens, chips: int = 1) -> float:
    d, _h, _n_lin, n_full, q, _kv, v = _dims(model)
    return (rows * (2.0 * (layer_matmul_weights(model) + v * d) + _step_flops_per_row(model))
            + 4.0 * n_full * q * context_tokens) / chips


def chunk_form_flops(model: dict, chunk: int) -> float:
    """The chunkwise rule over ``chunk`` tokens, all linear layers."""
    _d, _h, n_lin, *_ = _dims(model)
    H, dk, dv, _conv, _K = _mixer(model)
    C = min(SUB_CHUNK, chunk)
    per_sub = (4.0 * C * C * dk                 # K K^T and Q K^T
               + C * C * (dk + dv)              # (I + L) [U W] = [..] by substitution
               + 6.0 * C * dk * dv              # W S, (Q G) S, K^T U
               + 2.0 * C * C * dv)              # tril(Q K^T) U
    return n_lin * H * (chunk / C) * per_sub


def prefill_chunk_flops(model: dict, *, chunk, context_before, chips: int = 1) -> float:
    """The planes' matmuls, causal attention in the full layers over what each
    token may see, the chunk form in the linear ones; no head."""
    _d, _h, _n_lin, n_full, q, _kv, _v = _dims(model)
    attended = chunk * context_before + chunk * (chunk + 1) / 2.0
    return (2.0 * chunk * layer_matmul_weights(model) + 4.0 * n_full * q * attended
            + chunk_form_flops(model, chunk)) / chips


def prefill_chunk_bytes(model: dict, *, chunk, context_before, chips: int = 1,
                        kv_bytes: int = 2, scale_bytes: int = 2) -> float:
    _d, _h, _n_lin, n_full, _q, kv, _v = _dims(model)
    cache = 2 * n_full * kv * kv_bytes * (context_before + chunk)
    return (_weight_bytes(model, scale_bytes) + cache + state_bytes(model, 1)) / chips


def kernel_counts(model: dict, kernel: str, *, rows) -> dict | None:
    """Bytes and FLOPs of ONE call of a named kernel over ``rows`` rows, and
    how many calls one run of its program makes; None for a kernel this
    configuration does not have. ``gated_delta_step``: one linear layer's
    step form, each row's state read once and written once, q k v alpha beta
    in and o out in float32."""
    if kernel != STEP_KERNEL:
        return None
    _d, _h, n_lin, *_ = _dims(model)
    H, dk, dv, _conv, _K = _mixer(model)
    vectors = H * (2 * dk + 4 * dv) * 4
    return {"bytes": rows * (2.0 * H * dk * dv * 4 + vectors), "flops": 7.0 * rows * H * dk * dv,
            "calls_per_program": n_lin}
