"""The system under test, built the way ``chip_smoke.py`` proved on the
chip (PR 21). ``build_engine``, ``assert_no_recovery``,
``count_compile_seconds`` and ``peak_bytes`` are copies of the smoke's, so
that a later change to the smoke cannot move the yardstick. The weights
are NOT the smoke's: they are the reference module's, made on the device
in one jitted call and wrapped in the program's containers.
"""

from __future__ import annotations

import functools

WATCHDOG_FLOOR_S = 600.0     # a cold 7B executable compiles inside a dispatch


def device_or_exit(chips: int):
    """The TPU devices, or an exit with one line and no result."""
    import sys

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"benchmarks/run.py: no TPU (jax.devices()[0].platform == "
                 f"{devices[0].platform!r}); a benchmark run needs the chip")
    if len(devices) < chips:
        sys.exit(f"benchmarks/run.py: the cell asks for {chips} chips, JAX "
                 f"reports {len(devices)}")
    return devices


def program_config(spec, check: bool = True):
    """The program's own preset, checked number by number against the
    configuration file (the file is what is run). ``check=False`` is for
    the tests, which shrink the sizes: the preset then takes the spec's."""
    import dataclasses

    from lir_tpu.models import registry

    cfg = registry.REGISTRY[spec.preset]()
    if not check:
        return dataclasses.replace(
            cfg, vocab_size=spec.vocab, hidden_size=spec.d,
            n_layers=spec.layers, n_heads=spec.heads,
            n_kv_heads=spec.kv_heads, head_dim=spec.head_dim,
            intermediate_size=spec.ffn)
    same = {"vocab_size": spec.vocab, "hidden_size": spec.d,
            "n_layers": spec.layers, "n_heads": spec.heads,
            "n_kv_heads": spec.kv_heads, "head_dim": spec.head_dim,
            "intermediate_size": spec.ffn, "gated_mlp": spec.gated,
            "activation": spec.act, "norm": spec.norm, "norm_eps": spec.eps,
            "parallel_block": spec.parallel, "shared_block_ln": spec.parallel,
            "tie_embeddings": spec.tied, "rope_theta": spec.rope_theta,
            "pos_embedding": "rotary", "rotary_pct": 1.0,
            "kv_cache_int8": False}
    for key, want in same.items():
        got = getattr(cfg, key)
        if got != want:
            raise ValueError(f"{spec.name}: the program's preset "
                             f"{spec.preset!r} has {key}={got!r}, the "
                             f"configuration file says {want!r}")
    return cfg


def build_params(spec, ref, seed: int):
    """The served tree: the reference module's weights, every layer in one
    jitted call on the device, in the program's layout and containers."""
    import jax
    import jax.numpy as jnp

    from lir_tpu.models.quant import QuantTensor

    @functools.partial(jax.jit, static_argnums=(0,))
    def make(spec, key):
        layers = jax.vmap(lambda l: ref.layer_weights(spec, key, l))(
            jnp.arange(spec.layers))
        return layers, ref.top_weights(spec, key)

    def wrap(tree):
        return {name: (QuantTensor(q=leaf["q"], scale=leaf["scale"])
                       if isinstance(leaf, dict) and "q" in leaf else leaf)
                for name, leaf in tree.items()}

    layers, top = make(spec, ref.seed_key(seed))
    params = wrap(top)
    params["layers"] = wrap(layers)
    jax.block_until_ready(params)
    return params


def build_engine(params, cfg, runtime: dict):
    from lir_tpu.backends.fake import FakeTokenizer
    from lir_tpu.config import RuntimeConfig
    from lir_tpu.engine.runner import ScoringEngine

    rt = RuntimeConfig(watchdog_floor_s=WATCHDOG_FLOOR_S, **runtime)
    # The word tokenizer covers the whole vocabulary with no network;
    # harness/tokenizer.py restates its rule for the reference.
    return ScoringEngine(params, cfg, FakeTokenizer(vocab=cfg.vocab_size),
                         rt)


def assert_no_recovery(engine, where: str) -> None:
    """A run in which the program recovered from a fault, degraded a
    dispatch, stalled or reclaimed memory measured something else."""
    f, g, gov = engine.fault_stats, engine.guard_stats, engine.governor
    clean = {
        "faults": (f.recovered_dispatches == 0 and f.degraded_dispatches == 0
                   and f.degraded_rows == 0, f.summary),
        "stalls": (not sum(g.stalls.values()), lambda: g.stalls),
        "quarantined": (not sum(g.quarantined.values()),
                        lambda: g.quarantined),
        "governor": (gov.stats.oom_reclaims == 0
                     and gov.stats.oom_exhausted == 0, gov.stats.summary),
    }
    for name, (ok, detail) in clean.items():
        if not ok:
            raise RuntimeError(f"{where}: the program recovered ({name}): "
                               f"{detail()}")


# Seconds JAX spent compiling (or loading from the persistent cache) and
# how many programs, as its own monitoring events report them.
COMPILE = {"seconds": 0.0, "programs": 0}


def count_compile_seconds() -> None:
    import jax

    def on_duration(event: str, seconds: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            COMPILE["seconds"] += seconds
            COMPILE["programs"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def peak_bytes() -> tuple:
    """(peak bytes in use, bytes limit) of the fullest local chip."""
    import jax

    peak = limit = 0
    for d in jax.local_devices():
        s = d.memory_stats() or {}
        if int(s.get("peak_bytes_in_use", 0)) >= peak:
            peak = int(s.get("peak_bytes_in_use", 0))
            limit = int(s.get("bytes_limit", 0))
    return peak, limit


def in_use() -> int:
    """Bytes in use now on the fullest local chip."""
    import jax

    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in jax.local_devices())
