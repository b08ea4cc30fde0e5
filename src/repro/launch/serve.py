"""Serving driver: batched decode with the slot server.

``python -m repro.launch.serve --arch tinyllama-1.1b --smoke --requests 8``
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--fake-devices", type=int, default=0)
    args = ap.parse_args()
    if args.fake_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.fake_devices}")
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.models import get_model
    from repro.serve import BatchedServer

    mod = configs.load(args.arch)
    cfg = mod.SMOKE if args.smoke else mod.CONFIG
    if args.smoke:
        cfg = cfg.scaled(dtype=jnp.float32)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    srv = BatchedServer(model, params, slots=args.slots,
                        max_len=args.max_len)
    rng = np.random.default_rng(0)
    reqs = [srv.submit(rng.integers(0, cfg.vocab, size=rng.integers(2, 8)),
                       max_new=args.max_new)
            for _ in range(args.requests)]
    import time
    t0 = time.time()
    steps = srv.run()
    dt = time.time() - t0
    toks = sum(len(r.out) for r in reqs)
    print(f"served {len(reqs)} requests, {toks} tokens, "
          f"{steps} batch steps, {toks / dt:.1f} tok/s")
    for r in reqs[:4]:
        print(f"  req {r.rid}: prompt={r.prompt.tolist()} -> {r.out[:8]}...")


if __name__ == "__main__":
    main()
