"""Offline flight-bundle replay — re-execute a captured slow statement
and assert bit-identical results.

The flight recorder (cloudberry_tpu/obs/flightrec.py) captures a slow
or erroring statement's debug bundle, including a sha256 digest over
the DECODED result columns. This tool closes the forensics loop: given
a bundle (a file saved from ``meta "flight"``, or the export list
itself), it opens a fresh session against the bundle's durable store,
re-executes the sql, and compares digests — the replay contract from
docs/DESIGN.md "Capacity & forensics plane":

    same store version + same statement text + same config shape
    ⇒ the same bytes, or the replay FAILS loudly.

A digest mismatch means the store moved underneath (a later commit),
the engine regressed, or the bundle is from a different cluster — all
three are exactly what a forensics session needs to know first.

Usage:
    python tools/flight_replay.py bundle.json [--index N] [--root DIR]
        [--segments N]

``bundle.json`` may hold one bundle, a list, or a ``meta "flight"``
response ({"flights": [...]}); --index picks from a list (default 0,
the newest). --root / --segments override the bundle's recorded store
root and mesh width (e.g. the store was copied for offline analysis).
Exit 0 on a bit-identical replay, 1 on mismatch, 2 on an unreplayable
bundle (no store root, no result digest, or non-JSON bind params).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def pick_bundle(doc, index: int = 0) -> dict:
    """One bundle out of whatever shape the file holds."""
    if isinstance(doc, dict) and "flights" in doc:
        doc = doc["flights"]
    if isinstance(doc, dict) and "meta" in doc \
            and isinstance(doc["meta"], dict):
        doc = doc["meta"].get("flights", doc)
    if isinstance(doc, list):
        if not doc:
            raise ValueError("empty flight list")
        return doc[index]
    if isinstance(doc, dict):
        return doc
    raise ValueError(f"unrecognized bundle document: {type(doc).__name__}")


def replay(bundle: dict, session=None, root: str | None = None,
           n_segments: int | None = None) -> dict:
    """Re-execute one bundle; returns the verdict record:
    ``{"ok": bool, "expected": digest, "got": digest, ...}``.
    ``session`` overrides session construction (tests pass the live
    session to assert replay-on-the-same-engine first)."""
    from cloudberry_tpu.obs import flightrec

    expected = bundle.get("result")
    if expected is None:
        return {"ok": False, "unreplayable":
                "bundle has no result digest (errored or DML statement)"}
    params = bundle.get("params") or {}
    if session is None:
        store_root = root or bundle.get("storage_root")
        if not store_root:
            return {"ok": False, "unreplayable":
                    "bundle has no storage root (in-memory session) — "
                    "pass --root to point at a copied store"}
        import cloudberry_tpu as cb
        from cloudberry_tpu.config import Config

        nseg = n_segments if n_segments is not None \
            else int(bundle.get("n_segments", 1))
        session = cb.Session(Config().with_overrides(**{
            "storage.root": store_root, "n_segments": nseg}))
    out = session.sql(bundle["sql"], **params)
    got = flightrec.result_digest(out)
    ok = bool(got is not None
              and got["sha256"] == expected.get("sha256")
              and got["rows"] == expected.get("rows"))
    return {"ok": ok, "expected": expected, "got": got,
            "sql": bundle["sql"][:200]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("bundle", help="bundle JSON file (one bundle, a "
                                   "list, or a meta 'flight' response)")
    ap.add_argument("--index", type=int, default=0,
                    help="which bundle when the file holds a list "
                         "(0 = newest)")
    ap.add_argument("--root", default=None,
                    help="override the bundle's storage root")
    ap.add_argument("--segments", type=int, default=None,
                    help="override the bundle's segment count")
    args = ap.parse_args(argv)

    with open(args.bundle) as fh:
        bundle = pick_bundle(json.load(fh), args.index)
    # The replay contract is bit-identity, and float aggregates are only
    # bit-stable on ONE backend: the replay runs wherever JAX puts it
    # (set JAX_PLATFORMS to match the engine that captured the bundle)
    # and says where that was — never a silent CPU default.
    from cloudberry_tpu.parallel.mesh import device_line

    print(f"replaying on {device_line()}", file=sys.stderr)
    verdict = replay(bundle, root=args.root, n_segments=args.segments)
    if verdict.get("unreplayable"):
        print(f"UNREPLAYABLE: {verdict['unreplayable']}", file=sys.stderr)
        return 2
    if verdict["ok"]:
        print(f"OK: bit-identical replay "
              f"({verdict['expected']['rows']} rows, "
              f"sha256 {verdict['expected']['sha256'][:16]}…)")
        return 0
    print("MISMATCH:", file=sys.stderr)
    print(f"  expected {verdict['expected']}", file=sys.stderr)
    print(f"  got      {verdict['got']}", file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
