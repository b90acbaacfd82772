"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Boots the multi-tenant engine (MURS admission by default; ``--fair`` for
the stock baseline) and runs a synthetic two-tenant workload.  By default
it serves the reduced (smoke) config so the command runs anywhere; pass
``--full`` on a TPU for the published widths (random weights from a seed).
"""

import argparse
from typing import Optional

import jax

from repro.configs import ARCHS, get_arch
from repro.launch import enable_compile_cache
from repro.models import init_model
from repro.sched import FairPolicy, MursConfig, MursPolicy
from repro.serve import EngineConfig, Request, ServingEngine
from repro.serve.kv_cache import kv_bytes_per_token


def build_engine(
    arch: str,
    *,
    full: bool,
    slots: int,
    max_seq: int,
    pool_tokens: Optional[int] = None,
    fair: bool = False,
) -> ServingEngine:
    """One serving replica of ``arch`` with bf16 weights drawn from
    ``PRNGKey(0)``: the published config with ``full``, its smoke config
    otherwise.  The KV pool holds ``pool_tokens`` token-equivalents; None
    sizes it to the ``slots × max_seq`` dense caches the engine allocates."""
    cfg = get_arch(arch) if full else get_arch(arch).smoke()
    params = init_model(cfg, jax.random.PRNGKey(0))
    if pool_tokens is None:
        pool_tokens = slots * max_seq
    return ServingEngine(
        cfg, params,
        EngineConfig(
            n_slots=slots,
            max_seq=max_seq,
            hbm_capacity_bytes=max(kv_bytes_per_token(cfg), 1.0) * pool_tokens,
            policy=(FairPolicy() if fair
                    else MursPolicy(MursConfig.for_serving(period=1.0))),
        ),
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b", choices=sorted(ARCHS))
    ap.add_argument("--fair", action="store_true", help="disable MURS")
    ap.add_argument("--full", action="store_true",
                    help="serve the published config (requires a TPU)")
    ap.add_argument("--slots", type=int, default=None,
                    help="batch slots (default 4; 8 with --full)")
    ap.add_argument("--max-seq", type=int, default=None,
                    help="tokens per slot (default 64; 2048 with --full)")
    ap.add_argument("--pool-tokens", type=int, default=None,
                    help="KV pool capacity in token-equivalents "
                         "(default 80; slots × max-seq with --full)")
    ap.add_argument("--requests", type=int, default=7)
    args = ap.parse_args()

    enable_compile_cache()
    slots = args.slots or (8 if args.full else 4)
    max_seq = args.max_seq or (2048 if args.full else 64)
    engine = build_engine(
        args.arch,
        full=args.full,
        slots=slots,
        max_seq=max_seq,
        pool_tokens=args.pool_tokens or (None if args.full else 80),
        fair=args.fair,
    )
    n_a = args.requests // 2 + args.requests % 2
    for i in range(n_a):
        engine.submit(Request(f"A{i}", "A", list(range(10, 18)), 40))
    for i in range(args.requests - n_a):
        engine.submit(Request(f"B{i}", "B", list(range(30, 34)), 6))
    rep = engine.run(max_ticks=1000)
    mode = "FAIR" if args.fair else "MURS"
    print(f"[{mode}] completed {rep.completed}/{args.requests}  "
          f"failed {rep.failed}  "
          f"suspensions {rep.extras['suspensions']}  "
          f"tokens {rep.tokens_generated}  "
          f"peak pool {rep.extras['peak_used_fraction']:.2f}")


if __name__ == "__main__":
    main()
