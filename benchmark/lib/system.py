"""The system under test, built from a configuration file.

The only module of the benchmark that imports the program. From it the
benchmark takes the model, the trainer, the serve engine, their
counters — and nothing that measures.

Seeds: `--seed` makes the weights (`FFConfig.seed` seeds the parameter
initialisers) and the data. It never reaches the search:
`FFConfig.search_budget` stays 0, and where a configuration asks for a
search the benchmark calls `search.mcmc.optimize*` itself with the
configuration's own `search_seed`, then compiles with the strategy
found. (In the program `FFConfig.seed` feeds both.)
"""

from __future__ import annotations

import collections
import functools
import json
import time


def weight_seed(seed: int) -> int:
    """Any non-negative `--seed` folded into what `jax.random.PRNGKey`
    takes without 64-bit mode (the driver's seeds pass 2**31)."""
    return int(seed) % 2147483647


def build_lm(conf: dict, seed: int, *, batch: int, mesh=None):
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import build_transformer_lm
    cfg = FFConfig(batch_size=batch, seed=weight_seed(seed),
                   search_budget=0, **conf["system"])
    return build_transformer_lm(
        cfg, vocab_size=conf["vocab_size"],
        max_seq_len=conf["max_position_embeddings"],
        hidden=conf["hidden_size"], num_heads=conf["num_attention_heads"],
        num_layers=conf["num_hidden_layers"], ff_dim=conf["ffn_dim"],
        mesh=mesh)


def build_engine(conf: dict, seed: int, interpret: bool = False):
    """The serve engine over freshly initialised weights, its one mixed
    program warmed. -> (engine, seconds spent in warmup())."""
    from flexflow_tpu.config import CompMode
    from flexflow_tpu.serve import ServeEngine
    lm = build_lm(conf, seed, batch=1)
    lm.compile(comp_mode=CompMode.INFERENCE)
    eng = ServeEngine(lm, interpret=interpret)
    t0 = time.perf_counter()
    eng.warmup()
    return eng, time.perf_counter() - t0


def expected_attn_impl(interpret: bool) -> str:
    from flexflow_tpu.kernels.paged_ragged_v2 import PALLAS, PALLAS_INTERPRET
    return PALLAS_INTERPRET if interpret else PALLAS


def build_trainer(conf: dict, seed: int, devices, say):
    """The trainer, compiled under the layout the configuration's search
    returns. The search's outcome (mesh, layout tally, engine) goes out
    through `say` BEFORE compile places any state, so a run that dies
    there has still named its layout. -> (model, info)."""
    import jax
    from flexflow_tpu import AdamOptimizer, make_mesh
    from flexflow_tpu.core.losses import sparse_categorical_crossentropy
    from flexflow_tpu.search import mcmc

    tr = conf["train"]
    n = len(devices)
    shape = tuple(tr.get("mesh_shape") or (1, n))
    mesh = make_mesh(shape, ("data", "model"), devices)
    lm = build_lm(conf, seed, batch=int(tr["global_batch"]), mesh=mesh)
    cfg = lm.config
    t0 = time.perf_counter()
    budget = int(tr.get("search_budget", 0))
    strategy = None
    if budget > 0:
        strategy = mcmc.optimize(lm, budget=budget, alpha=cfg.search_alpha,
                                 mesh=mesh, seed=int(tr["search_seed"]))
    search_s = time.perf_counter() - t0
    stats = lm.search_stats or {}
    # the layout as a tally: how many ops carry each axis map
    layout = collections.Counter(
        json.dumps(strategy.for_op(op.name).axis_map, sort_keys=True)
        for op in lm.ops) if strategy is not None else {}
    opt = tr["optimizer"]
    info = {"mesh": dict(mesh.shape), "search_budget": budget,
            "search_seed": tr.get("search_seed"),
            "search_engine": stats.get("engine", "no search ran"),
            "search_s": search_s, "layout": dict(layout),
            "optimizer": opt["name"]}
    say("layout", info)
    if opt["name"] != "adam":
        raise SystemExit(f"benchmark: unknown optimizer {opt['name']!r}")
    optimizer = AdamOptimizer(lr=opt["lr"], beta1=opt["beta1"],
                              beta2=opt["beta2"], epsilon=opt["epsilon"])
    # the graph ends in the head's logits, not in a softmax
    lm.compile(optimizer=optimizer, loss_type=functools.partial(
        sparse_categorical_crossentropy, from_logits=True), metrics=[],
        mesh=mesh, strategy=strategy)
    say("placement", {"param_specs": sorted(
        {str(leaf.sharding.spec)
         for leaf in jax.tree_util.tree_leaves(lm.state.params)
         if hasattr(leaf.sharding, "spec")})})
    return lm, info
