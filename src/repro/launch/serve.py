"""Serving launcher: load (or init) a model, prefill a batch of prompts,
decode with the KV/SSM cache engine.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
      --prompt "In the beginning " --steps 32

When the checkpoint directory carries serving state (written by
``checkpoint.store.save_serving_state`` — the (plan, version, calibration)
triple a training-while-serving engine publishes), the engine resumes at
the published version with the published plan tables instead of replanning
from scratch (``--no-serve-state`` opts out).

``--replicas N`` brings up a FLEET instead of a single engine: N named
replicas behind a ``repro.serve.bus.PublicationBus`` (one shared host
group, so the bus's same-host dedup applies), an initial publication
broadcast through the bus, prompts routed to the healthy replicas, and a
per-replica health report at the end.

The model runs on a one-device ``(data, model)`` mesh through the sparse
MoE layer, with the Pallas kernels on (compiled on a TPU, interpret mode
on the CPU).

``--continuous`` serves through the continuous-batching
``repro.serve.scheduler.RequestScheduler`` instead of fixed-batch
``Engine.generate``: each prompt keeps its TRUE length (no padding
tokens through the model), prefill is one-shot, and sequences retire
individually the tick they finish.  Decoder-only archs only — the
scheduler's paged KV pool has no encoder cross-attention cache.
"""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve with N engine replicas behind a "
                         "PublicationBus (default: 1, no bus)")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--no-serve-state", action="store_true",
                    help="ignore persisted (plan, version) serving state")
    ap.add_argument("--prompt", action="append", default=None)
    ap.add_argument("--continuous", action="store_true",
                    help="serve through the paged-KV continuous-batching "
                         "scheduler (unpadded mixed-length prompts) "
                         "instead of fixed-batch generate")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax
    import numpy as np

    import repro.configs as configs
    from repro.checkpoint import store
    from repro.core import moe as moe_core
    from repro.launch import inputs as inp
    from repro.launch.mesh import make_debug_mesh
    from repro.models import model as mdl
    from repro.serve.engine import Engine

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get(args.arch))
    params = mdl.init_params(cfg, jax.random.PRNGKey(args.seed))
    pa, version = None, 0
    if args.checkpoint_dir:
        # verify=True: a corrupt newest checkpoint falls back to the
        # newest intact step (same walk train resume uses) instead of
        # raising CheckpointCorruptError out of restore at startup
        step = store.latest_step(args.checkpoint_dir, verify=True)
        if step is not None:
            target = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
            params = store.restore(args.checkpoint_dir, step,
                                   {"params": target})["params"]
            print(f"restored checkpoint step {step}")
        if not args.no_serve_state:
            # serving state must PAIR with the restored params: stale plan
            # tables (e.g. from before a reshard) describe a different row
            # ownership, so a step mismatch silently gathers wrong experts
            # — prefer the exact step, else fall back to a fresh plan
            serve_state = None
            if step is not None:
                serve_state = store.restore_serving_state(
                    args.checkpoint_dir, step=step)
                if serve_state is None and store.latest_serving_step(
                        args.checkpoint_dir) is not None:
                    print(f"serving state has no step {step} "
                          f"(params step); ignoring serving state")
            if serve_state is not None and int(
                    np.max(serve_state["pa"].owner_dev)) > 0:
                # plan from a multi-device (EP > 1) training run: this
                # launcher serves on a one-device mesh, where owner_row is
                # only meaningful per device — reading it flat would
                # gather wrong buffer rows.  Fall back to the fresh
                # single-device plan instead of silently decoding garbage.
                print("serving state is from an EP > 1 run; single-host "
                      "decode rebuilds a local plan instead")
                version = serve_state["version"]
                serve_state = None
            if serve_state is not None:
                pa = moe_core.tables_to_device(serve_state["pa"])
                version = serve_state["version"]
                print(f"restored serving state: step {serve_state['step']}"
                      f", version {version}")

    if cfg.moe.enabled and pa is None:
        # no persisted serving plan: single-host default (every expert
        # local) so MoE archs decode without a scheduler in the loop
        from repro.core.placement import (ep_materialization,
                                          homogeneous_sharding)
        sh = homogeneous_sharding(moe_core.num_moe_layers(cfg),
                                  cfg.moe.num_experts, 1)
        pa = moe_core.plan_to_arrays(ep_materialization(sh))
    # the runtime's materialization matches the plan's: extra slots mean a
    # sparse (ring) plan, none an expert-parallel one
    sparse = pa is not None and pa.extra_experts.shape[-1] > 0
    rt = inp.make_runtime(cfg, make_debug_mesh(1, 1),
                          impl="ring" if sparse else "ep", use_pallas=True)

    prompts = args.prompt or ["Hello world", "The scheduler said"]
    maxp = max(len(p) for p in prompts)
    enc = np.zeros((len(prompts), maxp), np.int32)
    for i, p in enumerate(prompts):
        b = np.frombuffer(p.encode(), np.uint8).astype(np.int32)
        enc[i, :len(b)] = b % cfg.vocab_size

    enc_in = None
    if cfg.is_encoder_decoder:
        if args.continuous:
            raise SystemExit("--continuous requires a decoder-only arch "
                             "(the paged KV pool has no encoder "
                             "cross-attention cache)")
        enc_in = np.random.default_rng(0).standard_normal(
            (len(prompts), cfg.encoder_seq_len, cfg.d_model)).astype(
            np.float32)

    def serve_continuous(eng):
        # each prompt at its true length: the scheduler batches mixed
        # lengths through per-sequence page tables, never decoding pads
        from repro.serve.scheduler import DONE, RequestScheduler
        with RequestScheduler(eng, max_slots=min(len(prompts), 4),
                              num_pages=-(-args.max_len // 8)
                              * min(len(prompts), 4) + 1,
                              page_size=8, max_kv=args.max_len,
                              default_ttl_s=600.0,
                              temperature=args.temperature,
                              seed=args.seed) as rs:
            reqs = [rs.submit(
                np.frombuffer(p.encode(), np.uint8).astype(np.int32)
                % cfg.vocab_size, max_new_tokens=args.steps)
                for p in prompts]
            rs.run()
            assert all(r.state == DONE for r in reqs), \
                [(r.state, r.finish_reason) for r in reqs]
            print(f"continuous batching: {rs.decode_ticks} decode ticks "
                  f"for {len(reqs)} requests")
            return [r.output() for r in reqs]

    if args.replicas <= 1:
        with Engine(cfg, rt, params, max_len=args.max_len, pa=pa,
                    version=version) as eng:
            out = (serve_continuous(eng) if args.continuous else
                   eng.generate(enc, steps=args.steps,
                                temperature=args.temperature,
                                seed=args.seed, encoder_input=enc_in))
    else:
        from repro.serve.bus import PublicationBus
        engines = [Engine(cfg, rt, params, max_len=args.max_len, pa=pa,
                          version=version, name=f"replica-{i}")
                   for i in range(args.replicas)]
        bus = PublicationBus([(e.name, e) for e in engines])
        try:
            # exercise the broadcast path once so the fleet promotes a
            # bus-published version before taking traffic
            bus.publish_params(params, version=version + 1, pa=pa,
                               wait=True)
            fleet = bus.route()   # healthy replicas, least-loaded first
            if not fleet:
                raise SystemExit("no healthy replicas after broadcast")
            out = (serve_continuous(fleet[0]) if args.continuous else
                   fleet[0].generate(enc, steps=args.steps,
                                     temperature=args.temperature,
                                     seed=args.seed,
                                     encoder_input=enc_in))
            for name, st in sorted(bus.poll().items()):
                print(f"replica {name}: {st.state.lower()} "
                      f"version {st.version}")
            print(f"fleet: {len(fleet)}/{args.replicas} healthy, "
                  f"{bus.dedup_hits} deduped builds")
        finally:
            bus.close()
            for e in engines:
                e.close()

    for i, p in enumerate(prompts):
        toks = out[i].tolist()
        text = bytes(t for t in toks if 0 < t < 128).decode(errors="replace")
        print(f"[{i}] {text!r}")


if __name__ == "__main__":
    main()
