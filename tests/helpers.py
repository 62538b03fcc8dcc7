"""Shared test fixtures: synthetic tiny .m/.t files built with the format writers."""

from __future__ import annotations

import os

import numpy as np

from dllama_tpu.formats import mfile, quants, tfile


def tiny_header_params(arch=mfile.ArchType.LLAMA, dim=64, n_layers=2, n_heads=4,
                       n_kv_heads=2, hidden_dim=96, vocab_size=128, seq_len=64,
                       head_dim=0, weight_type=quants.Q40, rope_type=mfile.RopeType.LLAMA,
                       n_experts=0, n_active_experts=0, **extra):
    """``extra`` adds/overrides raw header keys (e.g. rope_scaling_factor —
    the .m header stores them as ints, reference llm.cpp:85-88)."""
    params = {
        "version": 1,
        "arch_type": int(arch),
        "dim": dim,
        "hidden_dim": hidden_dim,
        "n_layers": n_layers,
        "n_heads": n_heads,
        "n_kv_heads": n_kv_heads,
        "vocab_size": vocab_size,
        "seq_len": seq_len,
        "hidden_act": int(mfile.HiddenAct.SILU),
        "rope_theta": 10000,
        "weight_float_type": weight_type,
        "rope_type": int(rope_type),
        "head_dim": head_dim,
        "norm_epsilon": 5,
        "n_experts": n_experts,
        "n_active_experts": n_active_experts,
    }
    params.update(extra)
    return params


def write_tensor(f, x: np.ndarray, float_type: int) -> None:
    flat = np.ascontiguousarray(x, dtype=np.float32).reshape(-1)
    if float_type == quants.F32:
        f.write(flat.tobytes())
    elif float_type == quants.F16:
        f.write(flat.astype(np.float16).tobytes())
    elif float_type == quants.Q40:
        f.write(quants.quantize_q40(flat))
    elif float_type == quants.Q80:
        f.write(quants.quantize_q80(flat))
    else:
        raise ValueError(float_type)


def write_tiny_model(path, params: dict, rng: np.random.Generator, scale=0.05):
    """Write a synthetic .m file with random weights; returns the dense weights."""
    dim = params["dim"]
    n_layers = params["n_layers"]
    n_heads = params["n_heads"]
    n_kv_heads = params["n_kv_heads"]
    hidden_dim = params["hidden_dim"]
    vocab = params["vocab_size"]
    head_dim = params.get("head_dim") or dim // n_heads
    q_dim = head_dim * n_heads
    kv_dim = head_dim * n_kv_heads
    wt = params["weight_float_type"]
    qwen3 = params["arch_type"] == int(mfile.ArchType.QWEN3)

    def rand(*shape):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    dense = {}
    with open(path, "wb") as f:
        mfile.write_header(f, params)

        def put(name, layer, x, ft):
            key = f"{name}.{layer}" if layer >= 0 else name
            dense[key] = x
            write_tensor(f, x, ft)

        put("embedding", -1, rand(vocab, dim), quants.F32)
        n_experts = params.get("n_experts", 0)
        for l in range(n_layers):
            put("block_matmul_q", l, rand(q_dim, dim), wt)
            put("block_matmul_k", l, rand(kv_dim, dim), wt)
            put("block_matmul_v", l, rand(kv_dim, dim), wt)
            put("block_matmul_wo", l, rand(dim, q_dim), wt)
            if n_experts > 0:
                put("block_moe_gate", l, rand(n_experts, dim), quants.F32)
                for e in range(n_experts):
                    put(f"block_expert_w3.{l}", e, rand(hidden_dim, dim), wt)
                    put(f"block_expert_w1.{l}", e, rand(hidden_dim, dim), wt)
                    put(f"block_expert_w2.{l}", e, rand(dim, hidden_dim), wt)
            else:
                put("block_matmul_w1", l, rand(hidden_dim, dim), wt)
                put("block_matmul_w2", l, rand(dim, hidden_dim), wt)
                put("block_matmul_w3", l, rand(hidden_dim, dim), wt)
            if qwen3:
                put("block_norm_q", l, 1.0 + rand(head_dim), quants.F32)
                put("block_norm_k", l, 1.0 + rand(head_dim), quants.F32)
            put("block_norm_0", l, 1.0 + rand(dim), quants.F32)
            put("block_norm_1", l, 1.0 + rand(dim), quants.F32)
        put("final_norm", -1, 1.0 + rand(dim), quants.F32)
        put("final_matmul_logits", -1, rand(vocab, dim), wt)
    return dense


def byte_vocab_tokenizer() -> tfile.TokenizerData:
    """A tokenizer whose regular vocab is all 256 bytes plus a few merges.

    Vocab layout mirrors the reference assumption: regular tokens first,
    bos at index `regular_vocab_size`, special tokens after.
    """
    vocab = [bytes([b]) for b in range(256)]
    scores = [0.0] * 256
    merges = [b"he", b"ll", b"llo", b"hello", b" w", b" wo", b" wor", b" worl",
              b" world", b"<|x|>"]
    for i, m in enumerate(merges[:-1]):
        vocab.append(m)
        scores.append(float(i + 1))
    bos_id = len(vocab)
    vocab += [b"<s>", b"</s>", merges[-1]]
    scores += [0.0, 0.0, 0.0]
    return tfile.TokenizerData(
        vocab=vocab, scores=scores, bos_id=bos_id, add_bos=True,
        eos_token_ids=[bos_id + 1],
        chat_template=None,
        max_token_length=max(len(t) for t in vocab),
    )


def pinned_host_probe():
    """Probe (once per process) which host memory kind this jaxlib can
    actually place arrays in: ``("pinned_host", "")`` when real pinned
    host memory works (the capability the offload weight path requires),
    falling back to ``("unpinned_host", reason)`` on builds that expose
    only that kind (CPU jaxlib — it IS host DRAM there, so the KV-tier
    spill/page-back tests exercise the real transfer path instead of
    capability-skipping), and ``(None, reason)`` when neither places.
    ``reason`` records why the stronger kind(s) failed. Delegates to the
    runtime's own CAPABILITY probe (``kvblocks.probe_host_memory_kind``
    — deliberately NOT the env-overridable ``host_memory_kind``: a
    forced serving knob like ``DLLAMA_KV_HOST_KIND=pinned_host`` must
    never flip capability-gated tests from skip to fail), so the tests
    and the serving tier can never disagree about what the backend can
    do."""
    from dllama_tpu.runtime.kvblocks import probe_host_memory_kind

    return probe_host_memory_kind()


def require_pinned_host():
    """``pytest.skip`` (with the probe's reason) when this jaxlib cannot
    place arrays in pinned_host memory specifically (the offload weight
    path's requirement — an unpinned fallback is not enough there)."""
    import pytest

    kind, reason = pinned_host_probe()
    if kind != "pinned_host":
        pytest.skip(f"jaxlib pinned_host unsupported on this backend: "
                    f"{reason}")


def require_host_memory() -> str:
    """``pytest.skip`` only when NO host memory kind places at all —
    the KV-tier tests run the real spill/page-back path on whatever kind
    the backend offers (``unpinned_host`` on the CPU tier). Returns the
    usable kind."""
    import pytest

    kind, reason = pinned_host_probe()
    if kind is None:
        pytest.skip(f"no jax host memory kind places on this backend: "
                    f"{reason}")
    return kind


def compile_paged_step(cfg, params, n_slots: int, n_blocks: int,
                        block_size: int, table_width: int, pool_dtype):
    """Compile ``paged_sampled_step_guarded`` with its cache donated, as the
    server's wrapper jits it, for the default backend from shapes alone
    (``params``: arrays or ``ShapeDtypeStruct``s). Returns ``(compiled,
    bytes of the K pool)``: a step that carries the pool through its layer
    scan and writes it in place holds no temporary
    (``compiled.memory_analysis().temp_size_in_bytes``) anywhere near a
    pool's size; one that stacks the pool out of the scan holds a whole
    second pool (k and v) there."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models import llama
    from dllama_tpu.runtime.kvblocks import PagedKVCache, StatePool

    S = jax.ShapeDtypeStruct
    shapes = lambda tree: jax.tree.map(lambda a: S(a.shape, a.dtype), tree)
    i32, f32 = jnp.int32, jnp.float32
    cache = pkv = shapes(PagedKVCache.create(cfg, n_blocks, block_size,
                                             dtype=pool_dtype))
    if cfg.has_state:
        cache = (pkv, shapes(StatePool.create(cfg, n_slots, pool_dtype)))
    B = n_slots
    compiled = jax.jit(llama.paged_sampled_step_guarded, static_argnums=1,
                       donate_argnums=(4,)).lower(
        shapes(params), cfg, S((B, 1), i32), S((B,), i32), cache,
        S((B, table_width), i32), S((B,), f32), S((B,), f32), S((B,), f32),
        S((), f32)).compile()
    return compiled, pkv.k.size * pkv.k.dtype.itemsize


def tiny_family_engine(folder: str, tmp_path, seed: int = 7):
    """An engine over ``benchmark/<folder>``'s selftest model (float32, a
    context of 512, blocks of 16), its planes drawn by the folder's own
    weights module from ``seed``: what every family's test file builds. The
    weights module's seam replaces ``runtime.engine.load_params_from_mfile``
    for the process; the caller puts it back."""
    import glob
    import importlib.util
    import json
    import sys

    from dllama_tpu.runtime.engine import InferenceEngine

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)        # as run.py puts it: a weights module imports its neighbours by name
    import run as bench_run

    (tiny,) = glob.glob(os.path.join(bench, folder, "selftest", "configs", "tiny-*.json"))
    with open(tiny, encoding="utf-8") as f:
        model = bench_run.model_view(json.load(f))
    spec = importlib.util.spec_from_file_location(f"{folder}_digest_weights", os.path.join(bench, folder, "weights.py"))
    weights = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(weights)
    path = str(tmp_path / f"tiny-{folder}.m")
    weights.write_sparse_model(path, model)
    weights.install_seam(seed)
    return InferenceEngine(path, None, max_seq_len=512, compute_dtype="float32", kv_block_size=16)


LOWERED_PROGRAMS = ("forward", "step", "tick")


def lowered_program_digest(cfg, params, program: str) -> str | None:
    """sha256 of the lowered text of one of a decoder family's three
    programs at one small geometry, from shapes alone: ``forward`` over a
    chunk of 32 into an admission's column of 512 positions (with its valid
    length where the family is paged-only), ``step``
    (``paged_sampled_step_guarded``) over 4 rows, 33 blocks of 16 (a window
    pool of 13), tables 8 wide, float32 pools, and ``tick`` (``family_of(cfg)
    .tick``, None where the family brings none) over both. What a refactoring
    of code that families share must leave as it was: take the digests on
    the parent commit with this same function and hold the change to them
    (``tests/goldens/program_hlo_sha256.json``). ``as_text()`` carries no
    source locations, so moving and renaming Python functions leaves it
    alone; the order of traced operations does not."""
    import hashlib

    import jax
    import jax.numpy as jnp

    from dllama_tpu.models import llama
    from dllama_tpu.models.family import family_of
    from dllama_tpu.runtime.kvblocks import PagedKVCache, StatePool

    S = jax.ShapeDtypeStruct
    shapes = lambda tree: jax.tree.map(lambda a: S(a.shape, a.dtype), tree)
    i32, f32 = jnp.int32, jnp.float32
    B, T, BS, M = 4, 32, 16, 8
    family = family_of(cfg)
    k = S((cfg.n_kv_layers, 1, cfg.cache_heads, 512, cfg.cache_width), f32)
    column = jax.eval_shape(lambda k: family.column(cfg, k, None if cfg.has_latent_cache else k), k)
    # what the step's cache is made of, in the order ``PagedGenerator._cache_parts`` has it
    cache = [shapes(PagedKVCache.create(cfg, 33, BS, dtype=f32))]
    if cfg.has_window_layers:
        cache.append(PagedKVCache(*(S((cfg.n_window_layers, 13, cfg.n_kv_heads, BS, cfg.head_dim), f32) for _ in "kv")))
    if cfg.has_state:
        cache.append(shapes(StatePool.create(cfg, B, f32)))
    if cfg.has_expert_share:
        from dllama_tpu.models.share import zero_totals

        cache.append(shapes(zero_totals(cfg)))
    cache = cache[0] if len(cache) == 1 else tuple(cache)
    tables = S((2, B, M) if cfg.has_window_layers else (B, M), i32)
    valid = (S((), i32),) if cfg.paged_only else ()
    rows = (S((B, 1), i32), S((B,), i32))
    if program == "forward":
        fn, args = llama.forward, (S((1, T), i32), S((), i32), column, *valid)
    elif program == "step":
        fn = llama.paged_sampled_step_guarded
        args = (*rows, cache, tables, S((B,), f32), S((B,), f32), S((B,), f32), S((), f32))
    elif family.tick is None:
        return None
    else:
        fn, args = family.tick, (*rows, (column, cache), tables, S((1, T), i32), S((), i32), *valid, S((), f32))
    lowered = jax.jit(lambda p, *a: fn(p, cfg, *a)).lower(shapes(params), *args)
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


def param_shapes(cfg, scales_dtype):
    """``Params`` of a dense decoder, a hybrid one or one with an SSD mixer
    beside attention as shapes: Q40 planes for
    every matmul of the layer stack(s), a dense head in the compute dtype.
    For compiling a program, not for running it."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models.hybrid import HybridLayers, LinearLayerParams
    from dllama_tpu.models.llama import LayerParams, Params
    from dllama_tpu.ops.linear import QuantizedWeight

    S = jax.ShapeDtypeStruct
    f32 = lambda *shape: S(shape, jnp.float32)
    dim, hid = cfg.dim, cfg.hidden_dim

    def q40(n, out, in_):
        return QuantizedWeight(scales=S((n, in_ // 32, out), scales_dtype),
                               codes=S((n, in_, out), jnp.int8))

    def full(n, norm_qk):
        return LayerParams(
            wq=q40(n, cfg.q_dim, dim), wk=q40(n, cfg.kv_dim, dim),
            wv=q40(n, cfg.kv_dim, dim), wo=q40(n, dim, cfg.q_dim),
            w1=q40(n, hid, dim), w2=q40(n, dim, hid), w3=q40(n, hid, dim),
            norm_att=f32(n, dim), norm_ffn=f32(n, dim), **norm_qk)

    if cfg.is_hybrid:
        NL, NF, H, K = (cfg.n_linear_layers, cfg.n_periods, cfg.lin_heads,
                        cfg.lin_conv_kernel)
        lin = LinearLayerParams(
            w_in=q40(NL, cfg.lin_in_dim, dim), w_ab=f32(NL, 2 * H, dim),
            conv_w=f32(NL, K, cfg.lin_conv_dim), a_log=f32(NL, H),
            dt_bias=f32(NL, H), norm_o=f32(NL, cfg.lin_value_dim),
            w_out=q40(NL, dim, H * cfg.lin_value_dim), w1=q40(NL, hid, dim),
            w2=q40(NL, dim, hid), w3=q40(NL, hid, dim),
            norm_att=f32(NL, dim), norm_ffn=f32(NL, dim))
        layers = HybridLayers(lin=lin, full=full(NF, dict(
            norm_q=f32(NF, cfg.q_dim), norm_k=f32(NF, cfg.kv_dim))))
    elif cfg.has_ssm:
        from dllama_tpu.models.falcon_h1 import FalconH1Layers

        L, H, d_ssm = cfg.n_layers, cfg.ssm_heads, cfg.ssm_inner_dim
        layers = FalconH1Layers(
            wq=q40(L, cfg.q_dim, dim), wk=q40(L, cfg.kv_dim, dim),
            wv=q40(L, cfg.kv_dim, dim), wo=q40(L, dim, cfg.q_dim),
            w_in=q40(L, cfg.ssm_in_dim, dim), w_dt=f32(L, H, dim),
            conv_w=f32(L, cfg.ssm_conv_kernel, cfg.ssm_conv_dim),
            conv_b=f32(L, cfg.ssm_conv_dim), a_log=f32(L, H),
            d_skip=f32(L, H), dt_bias=f32(L, H), norm_ssm=f32(L, d_ssm),
            w_out=q40(L, dim, d_ssm), w1=q40(L, hid, dim),
            w2=q40(L, dim, hid), w3=q40(L, hid, dim),
            norm_att=f32(L, dim), norm_ffn=f32(L, dim))
    else:
        layers = full(cfg.n_layers, dict(norm_q=None, norm_k=None))
    dense = jnp.dtype(cfg.compute_dtype)
    return Params(embedding=S((cfg.vocab_size, dim), dense), layers=layers,
                  final_norm=f32(dim), logits=S((cfg.vocab_size, dim), dense))


def delta_chunk_form(form: str):
    """The gated delta rule's chunk form by name: ``"xla"``, the jitted XLA
    twin, or ``"kernel"``, the Pallas kernel in interpret mode."""
    import jax

    from dllama_tpu.ops import gated_delta as gd

    if form == "xla":
        return jax.jit(gd.gated_delta_chunk_xla)
    return lambda *a: gd.gated_delta_chunk(*a, interpret=True)
