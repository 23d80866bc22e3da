#!/usr/bin/env python
"""Checkpoint weight-equality verifier CLI.

Capability parity with reference `tests/check_weights_equality.py` (232 ln):
compare the model weights of two checkpoints — any mix of vanilla
single-file and sharded (Orbax) formats — by key-set, then shape, then
max-abs-diff against ``--tolerance`` (default 1e-7, reference :71).
Exit codes match the reference: 0 = equal, 1 = different, 2 = error
(reference :224,228).

This is the harness behind the signature bit-exact-resume benchmark
(reference README.md:213-228): run straight-through vs interrupted+resumed,
then compare final checkpoints.

Usage:
  python tools/check_equality.py CKPT_A CKPT_B [--tolerance 1e-7] [--all-state]

By default only ``params`` leaves are compared (the reference compares model
weights only); ``--all-state`` extends to optimizer/RNG/counters, i.e. full
training-state equality.
"""

import argparse
import re
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _norm_key(keystr):
    """Normalize a leaf key-path string to a dotted path usable across
    formats: ".params['layers']['wq']" → "params.layers.wq"."""
    parts = re.findall(r"\.([A-Za-z_][A-Za-z0-9_]*)|\['([^']+)'\]|\[(\d+)\]", keystr)
    out = []
    for attr, key, idx in parts:
        out.append(attr or key or idx)
    return ".".join(out)


def load_vanilla(path):
    from pyrecover_tpu.checkpoint.vanilla import read_ckpt_raw

    _, paths, leaves = read_ckpt_raw(path, check_version=False)
    return {_norm_key(p): np.asarray(v) for p, v in zip(paths, leaves)}


def load_sharded(path):
    import jax
    import orbax.checkpoint as ocp

    with ocp.PyTreeCheckpointer() as ckptr:
        tree = ckptr.restore(Path(path).absolute() / "state")
    flat = {}
    for keypath, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        flat[_norm_key(jax.tree_util.keystr(keypath))] = np.asarray(leaf)
    return flat


def load_checkpoint(path):
    p = Path(path)
    if not p.exists():
        raise FileNotFoundError(p)
    return load_sharded(p) if p.is_dir() else load_vanilla(p)


def _as_bytes(x):
    return np.ascontiguousarray(x).reshape(-1).view(np.uint8)


def compare(a, b, tolerance, params_only=True, verbose=True):
    """Returns True if equal within tolerance (reference compare_weights,
    check_weights_equality.py:121-192: key-set → shape → max-abs-diff)."""
    if params_only:
        a = {k: v for k, v in a.items() if k.startswith("params.")}
        b = {k: v for k, v in b.items() if k.startswith("params.")}
    ok = True
    only_a, only_b = set(a) - set(b), set(b) - set(a)
    if only_a or only_b:
        ok = False
        if verbose:
            for k in sorted(only_a):
                print(f"KEY only in A: {k}")
            for k in sorted(only_b):
                print(f"KEY only in B: {k}")
    worst = (0.0, None)
    for k in sorted(set(a) & set(b)):
        va, vb = a[k], b[k]
        if va.shape != vb.shape:
            ok = False
            if verbose:
                print(f"SHAPE mismatch {k}: {va.shape} vs {vb.shape}")
            continue
        if va.dtype == vb.dtype and np.array_equal(
            _as_bytes(va), _as_bytes(vb)
        ):
            # byte-identical (the bit-exact-resume case): no float64
            # round trip — that conversion is minutes at a 7.6 GB state
            diff = 0.0
        else:
            diff = float(
                np.max(np.abs(va.astype(np.float64) - vb.astype(np.float64)))
            ) if va.size else 0.0
        if diff > worst[0]:
            worst = (diff, k)
        if diff > tolerance:
            ok = False
            if verbose:
                print(f"VALUE mismatch {k}: max abs diff {diff:.3e}")
    if verbose:
        if worst[1] is not None:
            print(f"Largest diff: {worst[0]:.3e} at {worst[1]}")
        print("EQUAL within tolerance" if ok else "DIFFERENT")
    return ok


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("checkpoint_a")
    ap.add_argument("checkpoint_b")
    ap.add_argument("--tolerance", type=float, default=1e-7)
    ap.add_argument("--all-state", action="store_true",
                    help="Compare the full training state, not just params")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)
    try:
        a = load_checkpoint(args.checkpoint_a)
        b = load_checkpoint(args.checkpoint_b)
        equal = compare(a, b, args.tolerance,
                        params_only=not args.all_state,
                        verbose=not args.quiet)
    except Exception as e:  # exit 2 = error (reference :228)
        print(f"ERROR: {e}", file=sys.stderr)
        return 2
    return 0 if equal else 1


if __name__ == "__main__":
    sys.exit(main())
