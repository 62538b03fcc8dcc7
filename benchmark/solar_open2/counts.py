"""Bytes and FLOPs of a decoder of delta-rule layers whose decay is a vector a
head beside gated full attention, routed experts of which a SHARE is held
behind every mixer: the ``counts`` module of ``solar-open2-250b``
(``solar_open2/README.md``), each function for ONE chip, counting what this
chip HOLDS (every mixer and attention head, ``n_routed_experts`` of the
``router_width`` experts, its rows of the vocabulary).

A delta-rule layer holds four planes (``q k v`` and the output projection, ``H
d`` wide each) and, in float32 rows, the two low-rank pairs, the ``beta`` rows and
the taps; a full layer ``q k v o`` and its gate's plane. Every layer holds the
router's rows over its whole width, the held experts (THREE planes each in
the model's width) and the shared one. What a dispatch must read of the routed
experts is the DISTINCT held experts its rows chose (:func:`experts_touched`,
under uniform routing), each once; what it must compute is the PAIRS that fall
on held experts. The cache is K and V of the FULL layers alone. A delta-rule
layer holds, per sequence, a float32 state ``H x d x d`` that a decode step
reads once and writes once, and the convolution's last ``K - 1`` inputs.

The chunk form's FLOPs are the chunkwise algorithm's own at sub-chunks of 64
with a decay a key channel (``olmo_hybrid/counts.py`` has the scalar case's): the
two ``C x C`` products cost a factor more on their diagonal blocks of 16, where
the decay's ratio is formed a channel a pair (3 FLOPs a channel a pair: the
exponent's difference, its product with ``k``, the sum; ``exp`` not counted),
and are one matmul between blocks.
"""

SUB_CHUNK = 64
SOLVE_BLOCK = 16
STEP_KERNEL = "gated_delta_step"
KERNEL = "expert_gemv"
CHUNK_KERNEL = "expert_chunk"
WALK_KERNEL = "paged_ragged_attention"


def _dims(model: dict) -> dict:
    d, hd = model["hidden_size"], model["head_dim"]
    lin = model["linear_attn_config"]
    H, ld = lin["num_heads"], lin["head_dim"]
    L, n_full = model["num_hidden_layers"], len(model["gqa_layers"])
    q, kv = model["num_attention_heads"] * hd, model["num_key_value_heads"] * hd
    wide, rank = model["moe_intermediate_size"], ld
    return {"d": d, "q": q, "kv": kv, "L": L, "n_full": n_full, "n_kda": L - n_full, "H": H, "ld": ld,
            "conv": 3 * H * ld, "taps": lin["short_conv_kernel_size"], "rank": rank,
            "kda": 4 * d * H * ld, "full": d * (3 * q + 2 * kv), "expert": 3 * d * wide, "shared": 3 * d * wide,
            "V": model["vocab_size"], "held": model["n_routed_experts"], "width": model["router_width"],
            "k": model["num_experts_per_tok"]}


def always_read_weights(model: dict) -> int:
    """Weights in the Q40 planes every dispatch reads whatever its routing:
    the mixers' four planes, q k v wo and the gate, the shared expert."""
    m = _dims(model)
    return m["n_kda"] * m["kda"] + m["n_full"] * m["full"] + m["L"] * m["shared"]


def float32_rows_bytes(model: dict) -> int:
    """The router's rows and bias over its whole width; a delta-rule layer's
    two low-rank pairs, ``beta`` rows, ``dt_bias`` and taps."""
    m = _dims(model)
    small = 2 * m["rank"] * m["d"] + 2 * m["H"] * m["ld"] * m["rank"] + m["H"] * m["d"] + m["H"] * m["ld"] \
        + m["taps"] * m["conv"]
    return (m["L"] * (m["width"] * m["d"] + m["width"]) + m["n_kda"] * small) * 4


def pairs_held(model: dict, rows: float) -> float:
    """Expected (row, expert) pairs a layer computes here for ``rows`` rows
    under uniform routing: ``rows k held / width``."""
    m = _dims(model)
    return rows * m["k"] * m["held"] / m["width"]


def experts_touched(model: dict, rows: float) -> float:
    """Expected DISTINCT held experts a layer's ``rows`` rows choose under
    uniform routing."""
    m = _dims(model)
    return m["held"] * (1.0 - (1.0 - m["k"] / m["width"]) ** rows)


def _plane_bytes(weights: float, scale_bytes: int) -> float:
    return weights * (1.0 + scale_bytes / 32.0)


def cached_token_bytes(model: dict, kv_bytes: int = 2) -> int:
    """Bytes of one cached token in one full layer: K and V."""
    return 2 * _dims(model)["kv"] * kv_bytes


def cached_token_flops(model: dict) -> float:
    """FLOPs one query token spends on one cached token in one full layer,
    all heads: the score and the value over a head's lanes."""
    return 4.0 * _dims(model)["q"]


def state_bytes(model: dict, rows: float, tail_bytes: int = 2) -> float:
    """One read and one write of ``rows`` sequences' recurrent state and
    convolution tails, all delta-rule layers."""
    m = _dims(model)
    return 2.0 * rows * m["n_kda"] * (m["H"] * m["ld"] * m["ld"] * 4 + (m["taps"] - 1) * m["conv"] * tail_bytes)


def _step_flops_per_row(model: dict) -> float:
    """The step form for one row: decay, ``S^T k``, the rank-one update, ``S^T q``."""
    m = _dims(model)
    return 7.0 * m["n_kda"] * m["H"] * m["ld"] * m["ld"]


def chunk_form_flops(model: dict, chunk: int) -> float:
    """The chunkwise rule with a decay a key channel over ``chunk`` tokens, all
    delta-rule layers."""
    m = _dims(model)
    d = m["ld"]
    C = min(SUB_CHUNK, chunk)
    b = min(C, SOLVE_BLOCK)
    per_sub = (2 * (3.0 * C * b * d + 2.0 * C * C * d)   # K K^T and Q K^T: diagonal blocks a channel a pair, the rest a matmul
               + C * C * 2 * d                           # (I + L) [U W] = [..] by substitution
               + 6.0 * C * d * d                         # W S, (Q G) S, K^T U
               + 2.0 * C * C * d)                        # tril(Q K^T) U
    return m["n_kda"] * m["H"] * (chunk / C) * per_sub


def decode_step_bytes(model: dict, *, rows, context_tokens, chips: int = 1,
                      kv_bytes: int = 2, scale_bytes: int = 2, head_bytes: int = 2) -> float:
    m = _dims(model)
    weights = _plane_bytes(always_read_weights(model) + m["L"] * experts_touched(model, rows) * m["expert"],
                           scale_bytes)
    cache = m["n_full"] * cached_token_bytes(model, kv_bytes) * context_tokens
    return ((weights + float32_rows_bytes(model) + m["V"] * m["d"] * head_bytes + cache) / chips
            + state_bytes(model, rows) + rows * m["d"] * 2)


def decode_step_flops(model: dict, *, rows, context_tokens, chips: int = 1) -> float:
    m = _dims(model)
    matmuls = 2.0 * (rows * (always_read_weights(model) + m["V"] * m["d"] + m["L"] * m["width"] * m["d"])
                     + m["L"] * pairs_held(model, rows) * m["expert"])
    return ((matmuls + m["n_full"] * cached_token_flops(model) * context_tokens) / chips
            + rows * _step_flops_per_row(model))


def _attended(chunk: float, context_before: float) -> float:
    """Sum over the chunk's tokens of the keys each sees."""
    return chunk * context_before + chunk * (chunk + 1) / 2.0


def prefill_chunk_flops(model: dict, *, chunk, context_before, chips: int = 1) -> float:
    m = _dims(model)
    matmuls = 2.0 * (chunk * (always_read_weights(model) + m["L"] * m["width"] * m["d"])
                     + m["L"] * pairs_held(model, chunk) * m["expert"])
    return ((matmuls + m["n_full"] * cached_token_flops(model) * _attended(chunk, context_before)) / chips
            + chunk_form_flops(model, chunk))


def prefill_chunk_bytes(model: dict, *, chunk, context_before, chips: int = 1,
                        kv_bytes: int = 2, scale_bytes: int = 2) -> float:
    m = _dims(model)
    weights = _plane_bytes(always_read_weights(model) + m["L"] * experts_touched(model, chunk) * m["expert"],
                           scale_bytes)
    cache = m["n_full"] * cached_token_bytes(model, kv_bytes) * (context_before + chunk)
    return (weights + float32_rows_bytes(model) + cache) / chips + state_bytes(model, 1)


def kernel_counts(model: dict, kernel: str, *, rows) -> dict | None:
    """``gated_delta_step``: bytes and FLOPs of ONE delta-rule layer's step form
    over ``rows`` rows (each row's state read once and written once; ``q k v`` and
    ``d`` decays a head in, ``beta`` in, ``o`` out, float32) and the calls one step
    program makes. ``expert_gemv``: bytes and FLOPs of ONE (row, expert) pair in
    one layer, its THREE planes read once, and the pairs a step of ``rows`` rows
    is EXPECTED to run here a layer. ``expert_chunk``: bytes of ONE held expert's
    three planes (what a run of pairs that share it fetches once) and the FLOPs
    of one pair. ``paged_ragged_attention``: bytes and FLOPs of ONE cached token
    one row's walk reads in ONE full layer. None for a kernel this
    configuration does not have."""
    m = _dims(model)
    if kernel == STEP_KERNEL:
        vectors = m["H"] * (5 * m["ld"] + 1) * 4         # q k v, the decays, o; beta
        return {"bytes": rows * (2.0 * m["H"] * m["ld"] * m["ld"] * 4 + vectors),
                "flops": 7.0 * rows * m["H"] * m["ld"] * m["ld"], "calls_per_program": m["n_kda"]}
    if kernel in (KERNEL, CHUNK_KERNEL):
        return {"bytes": _plane_bytes(m["expert"], 2), "flops": 2.0 * m["expert"],
                "pairs_per_layer": pairs_held(model, rows), "planes_per_layer": experts_touched(model, rows),
                "layers": m["L"], "calls_per_program": 3 * m["L"]}
    if kernel == WALK_KERNEL:
        return {"bytes": float(cached_token_bytes(model)), "flops": cached_token_flops(model),
                "layers": m["n_full"], "calls_per_program": m["n_full"]}
    return None
