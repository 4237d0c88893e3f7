#!/usr/bin/env python
"""Compile-cache control CLI (ISSUE 5).

    python tools/cache_ctl.py stats   [--dir D] [--json]
    python tools/cache_ctl.py prune   [--dir D] [--max-mb N | --all]
    python tools/cache_ctl.py prewarm ARTIFACT [--platform P]

`stats` prints the on-disk view of the persistent compile cache
(core/compile_cache.py): entry count, bytes vs budget, per-tag breakdown.
`prune` LRU-evicts down to a byte budget (default: the configured
PTPU_COMPILE_CACHE_MAX_MB), or clears everything with --all.
`prewarm ARTIFACT` AOT-compiles EVERY batch bucket of a serving artifact
(and its train module, when present) for this host's platform and writes
warm-start sidecars — run it on a new replica image ahead of first
traffic, and CompiledPredictor/BatchingPredictor/CompiledTrainer load
with zero traces and zero XLA compiles. Continuous-decode artifacts
(export_decode's, decode_signature.json) prewarm every program they
hold: the chunked-prefill programs (prefill_chunk_<C>/, one per chunk
size), the decode-step, block-copy (decode_blockcopy/) and zeros
programs — and, on speculative-decode artifacts, the verify program
(see below) — so DecodingPredictor replicas answer their first token
with zero compiles.

Quantized artifact tiers (ISSUE 11, export_compiled(quantize='int8')):
an artifact carrying an int8/ tier subdir (its own bucket tree +
signature) prewarms BOTH tiers automatically — every bf16 bucket, every
int8 bucket, and the int8 top mirror — so a replica serving either tier
(CompiledPredictor/BatchingPredictor tier='int8') starts with zero
compiles. Int8-KV decode artifacts (export_decode of a
kv_cache_dtype='int8' spec) prewarm like any other decode artifact:
the quantized cache is ordinary program state.

mp-sharded decode artifacts (ISSUE 13, build_decode_spec(mp_shard=k)):
an artifact whose signature carries a mesh
block prewarms over that mesh — the host must see prod(mesh axes)
devices of the artifact's platform or prewarm fails with exit 1 — and
writes MESH-TAGGED sidecars (aot_<platform>_<axes>.jaxexec, e.g.
aot_tpu_mp2.jaxexec) so a sharded executable can never load into an
unsharded serve or a different mesh shape. A --platform that contradicts
a sharded artifact's recorded platform is refused (sharded executables
are single-platform).

Speculative-decode artifacts (ISSUE 17, build_decode_spec(draft_k=K)):
a decode artifact whose signature carries a `verify` block (signature
version 3) ships a THIRD program, decode_verify/ — the [S, K+1] ->
[S, K+1, V] draft-scoring dispatch. Prewarm learns it exactly like the
step program it rides beside, across every tier and mesh tag the
artifact carries: bf16 and int8/ KV tiers, and
mesh-tagged sidecars for mp-sharded artifacts. A replica serving with a
drafter attached (DecodingPredictor(draft=...)) then reaches its first
verify tick — not just its first token — with zero compiles.
Version-2 artifacts (no verify block) prewarm unchanged.

Exit codes (all subcommands, including the decode, quantized-tier,
sharded/block-paged, and speculative verify-program prewarm paths):
  0  success (prewarm: at least one sidecar written)
  1  operation failed (compile error, unreadable module, no sidecar
     written, sharded artifact on a host without the full mesh's
     device count)
  2  usage error (unknown subcommand, missing/non-artifact directory —
     a dir carrying none of decode_signature.json / signature.json /
     train_module.jaxexport; a bare int8/ tier dir IS an artifact dir)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cmd_stats(args):
    from paddle_tpu.core import compile_cache as cc
    if args.dir:
        cc.enable(dir=args.dir)
    else:
        cc.enable()
    st = cc.disk_stats()
    if args.json:
        print(json.dumps(st, separators=(',', ':')))
        return 0
    print('cache dir : %s' % st['dir'])
    print('entries   : %d' % st['entries'])
    print('size      : %.2f MB entries + %.2f MB xla = %.2f MB '
          '(budget %.0f MB)'
          % (st['bytes'] / 2**20, st['xla_bytes'] / 2**20,
             st['total_bytes'] / 2**20, st['max_mb']))
    for tag in sorted(st['tags']):
        print('  tag %-16s %d' % (tag, st['tags'][tag]))
    if st['newest_use']:
        print('last use  : %s' % time.strftime(
            '%Y-%m-%d %H:%M:%S', time.localtime(st['newest_use'])))
    return 0


def _cmd_prune(args):
    from paddle_tpu.core import compile_cache as cc
    if args.dir:
        cc.enable(dir=args.dir)
    else:
        cc.enable()
    if args.all:
        n = cc.prune(clear=True)
    else:
        n = cc.prune(budget_mb=args.max_mb)
    st = cc.disk_stats()
    print('pruned %d items; %d entries remain (%.2f MB total)'
          % (n, st['entries'], st['total_bytes'] / 2**20))
    return 0


def _cmd_prewarm(args):
    if not os.path.isdir(args.artifact):
        print('prewarm: %s is not a directory' % args.artifact,
              file=sys.stderr)
        return 2
    # serve.py owns the artifact AOT contract; import it directly so
    # prewarm works on a serving host that carries only the deploy half
    from paddle_tpu.inference import serve
    decoding = serve._decoding_module()
    has_infer = os.path.exists(os.path.join(args.artifact,
                                            serve._SIGNATURE))
    has_train = os.path.exists(os.path.join(args.artifact,
                                            serve._TRAIN_MODULE))
    has_decode = os.path.exists(os.path.join(args.artifact,
                                             decoding._DECODE_SIGNATURE))
    if not has_infer and not has_train and not has_decode:
        print('prewarm: %s carries no exported module (missing %s / %s '
              '/ %s)' % (args.artifact, serve._SIGNATURE,
                         serve._TRAIN_MODULE, decoding._DECODE_SIGNATURE),
              file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    written = serve.precompile_artifact(args.artifact,
                                        platform=args.platform)
    dt = time.perf_counter() - t0
    for p in written:
        print('wrote %s (%d bytes)' % (p, os.path.getsize(p)))
    print('prewarmed %d module(s) in %.2fs' % (len(written), dt))
    return 0 if written else 1


def main(argv=None):
    # --help carries the full contract: the artifact layouts prewarm
    # understands (multi-bucket, decode two/three-program, quantized
    # int8/ tier) and the exit codes automation keys on
    ap = argparse.ArgumentParser(
        prog='cache_ctl.py', description=__doc__.split('\n')[0],
        epilog=__doc__[__doc__.index('Quantized artifact tiers'):],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = ap.add_subparsers(dest='cmd')
    p = sub.add_parser('stats', help='print on-disk cache statistics')
    p.add_argument('--dir', help='cache dir (default: configured)')
    p.add_argument('--json', action='store_true',
                   help='machine-readable output')
    p = sub.add_parser('prune', help='LRU-evict down to a byte budget')
    p.add_argument('--dir', help='cache dir (default: configured)')
    g = p.add_mutually_exclusive_group()
    g.add_argument('--max-mb', type=float, default=None,
                   help='evict down to this many MB (default: budget)')
    g.add_argument('--all', action='store_true', help='clear every entry')
    p = sub.add_parser('prewarm',
                       help='AOT-compile every bucket of a serving '
                            'artifact ahead of first traffic')
    p.add_argument('artifact', help='artifact dir (export_compiled / '
                                    'export_train_step output)')
    p.add_argument('--platform', default=None,
                   help="target platform (default: this host's backend)")
    args = ap.parse_args(argv)
    if args.cmd is None:
        ap.print_usage(sys.stderr)
        return 2
    try:
        return {'stats': _cmd_stats, 'prune': _cmd_prune,
                'prewarm': _cmd_prewarm}[args.cmd](args)
    except Exception as e:
        print('cache_ctl %s failed: %s: %s'
              % (args.cmd, type(e).__name__, e), file=sys.stderr)
        return 1


if __name__ == '__main__':
    sys.exit(main())
