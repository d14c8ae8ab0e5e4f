"""Compile the cells' main device programs at REAL widths for a DESCRIBED
v5e (``on-chip-measurement`` guide, section 2): what the chip's compiler
would refuse, such as a kernel over its VMEM or an engine program over
the chip's memory, it refuses here at no chip time. Nothing runs: these
are no chip runs.

Only what compiles in seconds is here: the served model's paged decode
and prefill programs with the cell's own engine sizes (the same check
refused 64 slots: PERF.md, Cells), and the flash kernels at the training
cell's head shape. The whole training steps take a minute each and stay
in PERF.md. One file, so that one worker loads the TPU's library.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import spec as S, weights as W

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def chip():
    """One described v5e device, or skip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_chip(monkeypatch):
    """Take the on-chip branches (interpret off, Pallas dispatch) and keep
    the persistent compile cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    from apex_tpu.ops import dispatch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dispatch._default_platform.cache_clear()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    dispatch._default_platform.cache_clear()


def _json(*parts):
    with open(os.path.join(S.HERE, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def engine():
    """The serving cell's engine over abstract weights; its eager arena
    (6.9 GB of zeros) is kept abstract too."""
    import apex_tpu.serve.engine as E
    from apex_tpu.models import TransformerLM
    cfg = _json("configs", "cerebras-gpt-1.3b.json")
    eng = _json("traffic", "chat-sysprompt-r0.6.json")["engine"]
    lm = TransformerLM(
        vocab_size=cfg["vocab_size"], max_seq_len=cfg["n_positions"],
        embed_dim=cfg["n_embd"], num_heads=cfg["n_head"],
        num_layers=cfg["n_layer"], ffn_mult=cfg["n_inner"] // cfg["n_embd"])
    params = jax.eval_shape(lambda: W.build(W.gpt2_specs(cfg), W.seed_key(0),
                                            BF16))
    real = E.init_paged_state
    E.init_paged_state = lambda *a, **k: jax.eval_shape(
        lambda: real(*a, **k))
    try:
        e = E.ContinuousBatchingEngine(
            lm, params, slots=eng["slots"], max_len=eng["max_len"],
            prefill_chunk=eng["prefill_chunk"], fused=True, paged=True,
            page_size=eng["page_size"], kv_pages=eng["kv_pages"],
            prefix_share=True, seed=0)
        state = e._init_state()
    finally:
        E.init_paged_state = real
    return e, params, state, cfg


def _on(chip, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=chip), tree)


def _fits(compiled, chip_bytes=15.75 * 2 ** 30):
    m = compiled.memory_analysis()
    live = m.argument_size_in_bytes + m.output_size_in_bytes \
        - m.alias_size_in_bytes
    assert live < chip_bytes
    return m


def test_paged_decode_program_of_the_served_model(chip, for_chip, engine):
    e, params, state, cfg = engine
    table = np.zeros((e.slots, e.max_pages), np.int32)
    compiled = e._decode_fn.lower(_on(chip, params), _on(chip, state),
                                  table).compile()
    assert compiled.as_text().count("tpu_custom_call") == cfg["n_layer"]
    m = _fits(compiled)
    arena = 2 * cfg["n_layer"] * (e.kv_pages + 1) * e.page_size \
        * cfg["n_embd"] * 2
    assert m.alias_size_in_bytes >= arena       # the arena is updated in place


@pytest.mark.parametrize("width", [1, 32])
def test_paged_prefill_program_of_the_served_model(chip, for_chip, engine,
                                                   width):
    """Width 32 is the engine's widest (= slots): at 64 slots and 1600
    pages this compile ran out of memory by 8.65 GB."""
    e, params, state, cfg = engine
    assert width in e._widths and max(e._widths) == 32
    c = e.prefill_chunk
    ids = np.arange(width, dtype=np.int32)
    table = np.zeros((e.slots, e.max_pages), np.int32)
    tv = np.ones((width,), bool)
    fh = jax.ShapeDtypeStruct((width, c, cfg["n_embd"]), BF16, sharding=chip)
    chunk = jax.ShapeDtypeStruct((width, c), jnp.int32, sharding=chip)
    compiled = e._prefill_batch_fns[width].lower(
        _on(chip, params), _on(chip, state), fh, ids, table[ids], chunk, 0,
        tv, tv).compile()
    _fits(compiled)


@pytest.mark.parametrize("bwd", [False, True], ids=["fwd", "bwd"])
def test_flash_kernels_at_the_training_cells_head_shape(chip, for_chip, bwd):
    from apex_tpu.contrib.multihead_attn import flash_attention
    cfg = _json("configs", "cerebras-gpt-1.3b-train.json")
    rows = _json("traffic", "train-fixed-8k.json")["per_chip"]
    hd = cfg["n_embd"] // cfg["n_head"]
    assert hd == 128
    shape = (rows * cfg["n_head"], cfg["input"]["seq"], hd)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)
    fn = fwd if not bwd else jax.grad(lambda q, k, v: jnp.sum(
        fwd(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2))
    args = [jax.ShapeDtypeStruct(shape, BF16, sharding=chip)] * 3
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= (3 if bwd else 1)
