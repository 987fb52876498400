"""Serving driver: stand up the full SWARM-LLM gateway on trained smokes.

Trains the three-tier swarm (probe + 2 peers, 1-hop curriculum), the cloud
FM tier (1+2-hop curriculum) and the safety classifier, then routes the
paper's 50-query study workload and prints Table III/IV/V-style metrics.

  PYTHONPATH=src python -m repro.launch.serve --train-steps 150
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import configs as C
from repro.core import safety as safety_lib
from repro.core.cost_model import LatencyParams
from repro.core.router import RouterConfig
from repro.core.uncertainty import UncertaintyConfig
from repro.data.pipeline import SyntheticLMPipeline
from repro.data.workload import FactWorld
from repro.models import transformer as T
from repro.serving.engine import InferenceEngine
from repro.serving.gateway import Gateway, run_cloud_only, run_edge_only
from repro.serving.simulator import NetworkSimulator, SimConfig
from repro.serving.swarm import SwarmExecutor
from repro.training import optimizer as opt
from repro.training import train as TR


def train_lm(arch: str, steps: int, *, two_hop: bool, seed: int,
             batch: int = 16, seq: int = 64, lr: float = 1e-2,
             num_layers: int | None = None, world: FactWorld | None = None):
    import dataclasses
    cfg = C.get_smoke(arch)
    cfg = dataclasses.replace(cfg, vocab_size=512)
    if num_layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=num_layers)
    ocfg = opt.AdamWConfig(lr=lr, total_steps=steps,
                           warmup_steps=max(steps // 10, 1), weight_decay=0.0)
    step_fn = TR.build_train_step(cfg, ocfg, None)
    params = T.init_params(cfg, jax.random.PRNGKey(seed))
    state = opt.init(params)
    pipe = SyntheticLMPipeline(batch, seq, two_hop=two_hop, seed=seed,
                               world=world)
    for step in range(steps):
        b = {k: jax.numpy.asarray(v) for k, v in pipe.get_batch(step).items()}
        params, state, m = step_fn(params, state, b)
    print(f"[serve] trained {arch} ({'2-hop' if two_hop else '1-hop'}) "
          f"final loss {float(m['loss']):.3f}")
    return cfg, params


def train_safety(steps: int = 150, seed: int = 5):
    from repro.training import optimizer as opt_lib
    cfg = safety_lib.classifier_config(vocab_size=512)
    params = safety_lib.init_safety(cfg, jax.random.PRNGKey(seed))
    state = opt_lib.init(params)
    trainer = safety_lib.make_trainer(cfg, lr=1e-2, steps=steps)
    world = FactWorld()
    for step in range(steps):
        # length 6 matches the study prompts: a single risk marker in a
        # short query must score below sigma (borderline cases, Table V SER)
        toks, labels = world.safety_training_batch(32, 6, step)
        params, state, loss = trainer(params, state, jax.numpy.asarray(toks),
                                      jax.numpy.asarray(labels))
    print(f"[serve] safety classifier BCE {float(loss):.3f}")
    return cfg, params


def calibrate_thresholds(probe: InferenceEngine, world: FactWorld,
                         base: RouterConfig, n: int = 24, max_new: int = 8
                         ) -> RouterConfig:
    """Fit τ_low/τ_high from the probe's U distribution on held-out queries
    (the paper tuned its 'final experiments' thresholds the same way,
    Sec. V-C).  τ_high at the 72.5th percentile targets the paper's ~28%
    escalation; τ_low at the 40th keeps the swarm path exercised."""
    import dataclasses as dc
    from repro.serving.swarm import pad_prompts
    qs = world.easy_queries(n, seed=101) + world.hard_queries(n, seed=102)
    res = probe.generate(pad_prompts([q["prompt"] for q in qs]), max_new)
    u = np.sort(res["u"])
    tau_low = float(np.quantile(u, 0.40))
    tau_high = float(np.quantile(u, 0.90))
    return dc.replace(base, tau_low=tau_low, tau_high=tau_high)


def build_gateway(train_steps: int = 150, quorum: int | None = None,
                  sim_cfg: SimConfig | None = None,
                  router_cfg: RouterConfig | None = None,
                  budget_total: float = 1.0, seed: int = 0,
                  world: FactWorld | None = None,
                  calibrate: bool = True, mesh=None,
                  engine_kw: dict | None = None):
    """Construct the full three-tier system (returns gateway + baselines).

    ``mesh`` (a ``launch.mesh.serving_mesh()`` (data, model) mesh) places
    every tier's engine on the mesh: greedy routing decisions and tokens
    are identical to the single-device gateway, but prefill/decode run
    SPMD-partitioned (see docs/SHARDING.md).

    ``engine_kw`` is forwarded to every tier's :class:`InferenceEngine`
    (e.g. ``paged=True``, ``attn_decode_impl=...``,
    ``compilation_cache_dir=...`` — see ``main()``'s flags).
    """
    engine_kw = engine_kw or {}
    # a compact fact world so the smoke-scale tiers genuinely memorise it
    world = world or FactWorld(n_ent=16, n_rel=6)
    ucfg = UncertaintyConfig(alpha=1.0, mode="distribution")
    # Tier-1 edge swarm: three heterogeneous SLMs (1-hop curriculum).
    # The probe (weakest member, paper's TinyLlama analogue) trains longest
    # to land near the paper's 0.45-easy edge tier; peers are stronger.
    probe_cfg, probe_p = train_lm("smollm-135m", 3 * train_steps,
                                  two_hop=False, seed=seed, world=world)
    e2_cfg, e2_p = train_lm("swarm-edge-1b", train_steps,
                            two_hop=False, seed=seed + 1, world=world)
    e3_cfg, e3_p = train_lm("qwen1.5-110b", train_steps,
                            two_hop=False, seed=seed + 2, world=world)
    # Tier-2 cloud FM: deeper + 2-hop curriculum + more steps
    fm_cfg, fm_p = train_lm("llama3-8b", int(2.25 * train_steps),
                            two_hop=True, seed=seed + 3, num_layers=4,
                            world=world)

    probe = InferenceEngine("probe-smollm", probe_cfg, probe_p, ucfg,
                            mesh=mesh, **engine_kw)
    peers = [probe,
             InferenceEngine("edge-1b", e2_cfg, e2_p, ucfg, mesh=mesh,
                             **engine_kw),
             InferenceEngine("edge-qwen", e3_cfg, e3_p, ucfg, mesh=mesh,
                             **engine_kw)]
    cloud = InferenceEngine("cloud-fm", fm_cfg, fm_p, ucfg, mesh=mesh,
                            **engine_kw)
    scfg, sparams = train_safety()

    rcfg = router_cfg or RouterConfig(tau_low=0.08, tau_high=0.22, sigma=0.7,
                                      peers_k=2, gamma=0.3, l_max=4.0)
    if calibrate and router_cfg is None:
        rcfg = calibrate_thresholds(probe, world, rcfg)
        print(f"[serve] calibrated tau_low={rcfg.tau_low:.3f} "
              f"tau_high={rcfg.tau_high:.3f}")

    sim = NetworkSimulator(sim_cfg or SimConfig(), LatencyParams(),
                           n_members=len(peers))
    from repro.data.workload import FACT_IS
    gw = Gateway(
        probe=probe, swarm=SwarmExecutor(peers, stop_token=FACT_IS),
        cloud=cloud,
        safety_params=sparams, safety_cfg=scfg, router_cfg=rcfg,
        sim=sim, budget_total=budget_total, quorum=quorum)
    return gw, probe, cloud, world


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--train-steps", type=int, default=400)
    ap.add_argument("--quorum", type=int, default=None)
    ap.add_argument("--budget", type=float, default=1.0)
    ap.add_argument("--model-parallel", type=int, default=0,
                    help="serve on a (data, model) mesh over the live "
                         "devices with this much tensor parallelism "
                         "(0 = single-device engines; on TPU the paged "
                         "decode kernel needs it to equal the device count, "
                         "docs/SHARDING.md)")
    ap.add_argument("--paged", action="store_true",
                    help="serve every tier off the paged block-pool cache "
                         "(docs/RUNTIME.md 'Paged caches & prefix sharing')")
    ap.add_argument("--attn-decode-impl", choices=("kernel", "gather"),
                    default=None,
                    help="paged decode-attention impl (implies --paged); "
                         "default: measured-best per backend — see "
                         "docs/RUNTIME.md 'Kernel-first decode'")
    ap.add_argument("--cache-quant", choices=("int8", "fp8"), default=None,
                    help="store paged KV blocks quantized with per-row f32 "
                         "scales (implies --paged); ~1.9x the sessions per "
                         "pool byte — see docs/RUNTIME.md 'Quantized caches'")
    ap.add_argument("--compilation-cache-dir", default=None,
                    help="persistent XLA compilation cache directory (a "
                         "relaunched gateway skips every already-seen jit); "
                         "a set JAX_COMPILATION_CACHE_DIR wins, the default "
                         "is .jax_cache/ in the checkout")
    args = ap.parse_args()
    from repro.compile_cache import enable_compilation_cache
    print(f"[serve] compilation cache "
          f"{enable_compilation_cache(args.compilation_cache_dir)}")

    mesh = None
    if args.model_parallel > 0:
        from repro.launch.mesh import serving_mesh
        mesh = serving_mesh(model_parallel=args.model_parallel)
        print(f"[serve] mesh {dict(mesh.shape)}")
    engine_kw = {}
    if (args.paged or args.attn_decode_impl is not None
            or args.cache_quant is not None):
        # the study workload batches ~50 queries through each tier, well
        # past the default pool sizing (16 full-length sessions) — give
        # the gateway engines headroom for the full workload batch
        engine_kw.update(paged=True, pool_blocks=1024,
                         attn_decode_impl=args.attn_decode_impl)
    if args.cache_quant is not None:
        engine_kw["cache_quant"] = args.cache_quant
    gw, probe, cloud, world = build_gateway(args.train_steps, args.quorum,
                                            budget_total=args.budget,
                                            mesh=mesh, engine_kw=engine_kw)
    queries = world.study_workload()

    log = gw.answer_batch(queries)
    # baselines graded on the SAME answer normalisation as the gateway
    stop = gw.swarm.stop_token
    edge = run_edge_only(queries, probe, gw.sim, stop_token=stop)
    cl = run_cloud_only(queries, cloud, gw.sim, stop_token=stop)

    print("\n=== Table III: latency & cloud usage ===")
    for name, lg in [("Edge-Only", edge), ("Cloud-Only", cl),
                     ("SWARM-LLM", log)]:
        print(f"{name:12s} mean {lg.latency.mean():5.2f}s  "
              f"p95 {np.percentile(lg.latency, 95):5.2f}s  "
              f"cloud {lg.cloud_usage()*100:5.1f}%")
    print("\n=== Table IV: accuracy ===")
    for name, lg in [("Edge-Only", edge), ("Cloud-Only", cl),
                     ("SWARM-LLM", log)]:
        print(f"{name:12s} overall {lg.accuracy():.3f}  "
              f"easy {lg.accuracy('easy'):.3f}  hard {lg.accuracy('hard'):.3f}")
    print("\n=== Table V: privacy (normalised to cloud-only) ===")
    pm = log.privacy()
    print(f"SWARM-LLM  CER {float(pm.cer):.3f}  TER {float(pm.ter):.3f}  "
          f"SER {float(pm.ser):.3f}")


if __name__ == "__main__":
    main()
