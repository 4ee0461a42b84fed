"""Serving launcher: drive an Infinite-LLM ``LLMServer`` open-loop on
synthetic traffic (smoke configs, CPU) or AOT-compile the production
serve step.

  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b \
      --instances 3 --requests 8
  PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-15b \
      --aot --shape decode_32k
"""
import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--instances", type=int, default=2)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--long-frac", type=float, default=0.2,
                    help="fraction of requests that exceed one instance")
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--shape", default="decode_32k",
                    choices=["prefill_32k", "decode_32k", "long_500k"])
    args = ap.parse_args()

    if args.aot:
        import os
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count"
                                   "=512")
        from repro.launch.dryrun import run_cell
        run_cell(args.arch, args.shape)
        return

    import jax
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.model import init_params
    from repro.serving import (Arrival, LLMServer, SamplingParams,
                               ServingConfig)

    enable_compile_cache()
    cfg = get_smoke_config(args.arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    server = LLMServer(params, cfg,
                       ServingConfig.smoke(n_instances=args.instances))
    # Open-loop synthetic traffic: Poisson-ish arrivals over ~1s.
    rng = np.random.default_rng(0)
    arrivals = []
    for i in range(args.requests):
        n = int(rng.integers(40, 70)) if rng.random() < args.long_frac \
            else int(rng.integers(4, 20))
        arrivals.append(Arrival(
            at=float(rng.uniform(0.0, 1.0)),
            prompt=rng.integers(0, cfg.vocab_size, size=n).tolist(),
            sampling=SamplingParams(max_new_tokens=args.max_new)))
    t0 = time.time()
    stats = server.run(arrivals)
    dt = time.time() - t0
    st = server.cluster.throughput_stats
    print(f"{stats['finished']:.0f}/{len(arrivals)} finished, "
          f"{stats['tokens']:.0f} tokens ({dt:.1f}s wall on "
          f"{jax.devices()[0].platform}); "
          f"ttft_p50={stats['ttft_p50'] * 1e3:.0f}ms "
          f"ttft_p99={stats['ttft_p99'] * 1e3:.0f}ms "
          f"tbt_p99={stats['tbt_p99'] * 1e3:.0f}ms")
    print(f"KV moved {st['kv_moved_bytes'] / 1024:.1f} KiB; "
          f"query/merge traffic {st['query_shipped_bytes'] / 1024:.1f} KiB")


if __name__ == "__main__":
    main()
