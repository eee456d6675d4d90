#!/usr/bin/env python
"""Checkpoint inspector: print and verify a checkpoint manifest.

Works on a single ``checkpoint_<serial>`` dir or a checkpoint root (then
every complete serial is listed and the newest inspected). Deliberately
jax-free — this is the tool an operator runs on a corrupt-checkpoint
page, possibly on a machine with no accelerator stack at all.

Knows all three dialects: plain training checkpoints
(resilience/checkpoint.py), the elastic sharded dialect
(elastic/reshard.py — mesh + per-shard digests + shard-byte sums), and
decode snapshots (serving/snapshot.py — slots/pages/refcounts/prefix
trie printed; ``--verify`` additionally re-checks page conservation
``free + unique-allocated == num_pages - 1`` and the refcount
accounting against the slot page lists + prefix trie).

    python tools/ckpt_inspect.py CKPT_DIR [--verify] [--json]

Exit codes:  0 ok · 1 usage/unreadable · 2 verification failed (digest
mismatch / missing file / no complete checkpoint) — the code the chaos
smoke and restore-time tooling gate on.
"""

import argparse
import hashlib
import json
import os
import sys

MANIFEST_NAME = "__manifest__.json"


def _sha256_file(path):
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


def _read_manifest(step_dir):
    try:
        with open(os.path.join(step_dir, MANIFEST_NAME)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _verify(step_dir, manifest):
    problems = []
    for name, meta in sorted(manifest.get("vars", {}).items()):
        shards = meta.get("shards")
        entries = shards if shards else [meta]
        shard_bytes = 0
        broken = False
        for ent in entries:
            fname = ent.get("file")
            if not fname:
                problems.append("no file recorded for var %r" % name)
                broken = True
                continue
            path = os.path.join(step_dir, fname)
            if not os.path.exists(path):
                problems.append("missing file for var %r: %s"
                                % (name, fname))
                broken = True
                continue
            want = ent.get("sha256")
            if want and _sha256_file(path) != want:
                problems.append("digest mismatch: var %r (%s)"
                                % (name, fname))
                broken = True
            shard_bytes += int(ent.get("bytes", 0))
        # per-var shard-byte cross-check: a dropped/truncated shard whose
        # digest still matches its (short) manifest entry would otherwise
        # reassemble silently short — reshard bugs must be diagnosable
        # OFFLINE, before a restore trips on them
        if (shards and not broken and meta.get("bytes") is not None
                and shard_bytes != int(meta["bytes"])):
            problems.append(
                "shard bytes of var %r sum to %d, manifest records %d"
                % (name, shard_bytes, int(meta["bytes"])))
    for fname in manifest.get("files", []):
        if not os.path.exists(os.path.join(step_dir, fname)):
            problems.append("missing file %s" % fname)
    return problems


def _decode_summary(ds):
    """Operator summary of a decode-snapshot manifest's dialect block
    (serving/snapshot.py): slots, pages, refcounts, prefix trie,
    backlog."""
    cfg = ds.get("config") or {}
    pool = ds.get("pool") or {}
    ref = pool.get("ref") or {}
    cache = ds.get("prefix_cache")
    beam = ds.get("beam")
    out = {
        "config": cfg,
        "steps_done": ds.get("steps_done"),
        "live_slots": sorted(int(k) for k in (ds.get("live") or {})),
        "free_slots": len(ds.get("free_slots") or []),
        "pages_free": len(pool.get("free") or []),
        "pages_allocated": len(ref),
        "pages_shared": sum(1 for c in ref.values() if int(c) > 1),
        "reserved_pages": ds.get("reserved_pages"),
        "leaked_pages": ds.get("leaked_pages"),
        "prefix_entries": (len(cache.get("entries") or [])
                           if cache else 0),
        "pending_requests": len(ds.get("pending") or []),
    }
    spec = ds.get("speculative")
    if spec or (cfg.get("speculative")):
        counters = (spec or {}).get("counters") or {}
        drafter = (spec or {}).get("drafter") or {}
        proposed = int(counters.get("proposed", 0))
        accepted = int(counters.get("accepted", 0))
        out["speculative"] = {
            "config": cfg.get("speculative"),
            "draft_params": len(drafter.get("params") or []),
            "proposed": proposed,
            "accepted": accepted,
            "dispatches": int(counters.get("dispatches", 0)),
            "acceptance_rate": (accepted / proposed
                                if proposed else None),
            "drafter": drafter.get("kind"),
            "draft_cached_slots": len(
                (drafter.get("state") or {}).get("dpos") or {}),
        }
    if beam:
        # beam bookkeeping: width, live lanes with hypothesis->slot
        # bindings, per-hypothesis scores/done (from the live map) and
        # the last parent permutation
        live = ds.get("live") or {}
        lanes = {}
        for lane, b in sorted((beam.get("lanes") or {}).items(),
                              key=lambda kv: int(kv[0])):
            slots = [int(x) for x in b.get("slots", [])]
            lanes[int(lane)] = {
                "slots": slots,
                "scores": [live.get(str(s), {}).get("score")
                           for s in slots],
                "done": [live.get(str(s), {}).get("done")
                         for s in slots],
                "last_parents": [
                    int(p) for p in (beam.get("last_parents") or {})
                    .get(str(lane), [])],
            }
        out["beam"] = {
            "width": beam.get("width"),
            "lanes": lanes,
            "free_lanes": len(beam.get("free_lanes") or []),
            "banked_results": len(beam.get("results") or []),
        }
    return out


def _decode_verify(ds, vars_meta=None):
    """Re-check the allocator laws a decode snapshot must satisfy:
    page conservation (free + unique-allocated == num_pages - 1, the
    seeded property test's invariant) and reference accounting (every
    page's refcount equals the references the slot page lists and the
    prefix trie actually hold on it). A torn/tampered dialect block
    must fail OFFLINE, before a restore builds a session on it."""
    problems = []
    cfg = ds.get("config") or {}
    pool = ds.get("pool") or {}
    num_pages = int(pool.get("num_pages", cfg.get("num_pages", 0)))
    free = [int(p) for p in pool.get("free") or []]
    ref = {int(p): int(c) for p, c in (pool.get("ref") or {}).items()}
    if len(free) + len(ref) != num_pages - 1:
        problems.append(
            "page conservation broken: %d free + %d allocated != %d "
            "(num_pages - 1)" % (len(free), len(ref), num_pages - 1))
    if set(free) & set(ref):
        problems.append("pages %s are both free and allocated"
                        % sorted(set(free) & set(ref)))
    held = {}
    for slot, pages in (ds.get("slot_pages") or {}).items():
        for p in pages:
            held[int(p)] = held.get(int(p), 0) + 1
    cache = ds.get("prefix_cache")
    for entry in (cache.get("entries") if cache else []) or []:
        page = int(entry[2])
        held[page] = held.get(page, 0) + 1
    # deliberately-LEAKED pages (failed rollback/COW dispatches keep
    # their pages allocated forever — corruption beats capacity) hold
    # refcounts with no slot/trie holder by DESIGN: they only need
    # ref >= visible holds, everything else must account exactly
    leaked = set(int(p) for p in ds.get("leaked_page_ids") or [])
    bad = sorted(
        p for p in set(held) | set(ref)
        if (ref.get(p, 0) < held.get(p, 0) if p in leaked
            else held.get(p, 0) != ref.get(p, 0)))
    if bad:
        problems.append(
            "refcount accounting broken at pages %s: slot lists + "
            "prefix trie hold %s, pool records %s (leaked: %s)"
            % (bad[:8], {p: held.get(p, 0) for p in bad[:8]},
               {p: ref.get(p, 0) for p in bad[:8]},
               sorted(leaked)[:8]))
    live_pages = sorted(int(p) for p in ds.get("live_pages") or [])
    if live_pages != sorted(ref):
        problems.append(
            "gathered live_pages %s disagree with pool refcounts %s"
            % (live_pages[:8], sorted(ref)[:8]))
    spec_cfg = cfg.get("speculative")
    if spec_cfg:
        # speculative cross-checks: the tree verifier reads every
        # RESIDENT row of a live slot through its page list, so a
        # tampered binding (a page dropped from the list, or rebound
        # while its rows are still claimed resident) must fail offline
        # even when it was laundered past the conservation and
        # refcount checks above by editing free/ref to match.
        spec = ds.get("speculative") or {}
        counters = spec.get("counters") or {}
        if int(counters.get("accepted", 0)) > int(
                counters.get("proposed", 0)):
            problems.append(
                "speculative counters tampered: accepted %d > "
                "proposed %d" % (int(counters.get("accepted", 0)),
                                 int(counters.get("proposed", 0))))
        ps = int(cfg.get("page_size") or 1)
        live = ds.get("live") or {}
        slot_pages = ds.get("slot_pages") or {}
        for slot, st in sorted(live.items(), key=lambda kv: int(kv[0])):
            pages = [int(p) for p in slot_pages.get(str(slot)) or []]
            pos = int(st.get("pos", 0))
            need = pos // ps + 1  # rows 0..pos the tree reads as base
            if len(pages) < need:
                problems.append(
                    "speculative slot %s: %d bound pages cannot back "
                    "%d resident rows (pos=%d page_size=%d) — tree "
                    "reads would hit unbound pages"
                    % (slot, len(pages), pos + 1, pos, ps))
            for page in pages[:need]:
                if ref.get(page, 0) < 1:
                    problems.append(
                        "speculative slot %s: resident page %d has no "
                        "refcount — tree-page binding is dangling"
                        % (slot, page))
        drafter = spec.get("drafter") or {}
        if drafter and drafter.get("kind") != spec_cfg.get("drafter"):
            problems.append(
                "speculative drafter state kind %r does not match "
                "config %r" % (drafter.get("kind"),
                               spec_cfg.get("drafter")))
        if drafter.get("kind") == "model" and vars_meta is not None:
            # the draft params steer acceptance timing, which binds
            # future backlog requests to slots (and slots key the
            # sampler) — a restore without them would silently change
            # the restored session's future streams
            for pname in drafter.get("params") or []:
                if ("spec_dparam__" + pname) not in vars_meta:
                    problems.append(
                        "draft param %r listed in the speculative "
                        "dialect but missing from the manifest vars"
                        % pname)
        dpos = (drafter.get("state") or {}).get("dpos") or {}
        for slot, wm in sorted(dpos.items(), key=lambda kv: int(kv[0])):
            if str(slot) not in live:
                problems.append(
                    "draft watermark on slot %s which is not live"
                    % slot)
                continue
            pos = int(live[str(slot)].get("pos", 0))
            if int(wm) > pos + 1:
                problems.append(
                    "draft watermark %d on slot %s runs past its "
                    "anchor pos %d — draft rows claim pages the "
                    "target never wrote" % (int(wm), slot, pos))
            # draft rows [0, wm) live in the draft pools through the
            # SAME page table — they need the same bound pages
            need = ((int(wm) - 1) // ps + 1) if int(wm) > 0 else 0
            pages = [int(p) for p in slot_pages.get(str(slot)) or []]
            if len(pages) < need:
                problems.append(
                    "draft watermark %d on slot %s outruns its %d "
                    "bound pages" % (int(wm), slot, len(pages)))
    beam = ds.get("beam")
    if beam:
        # beam-binding cross-check: every lane's hypothesis slots must
        # be lane-aligned, LIVE, and hold a page list the refcounts
        # above already accounted for — a lane pointing at a freed or
        # foreign slot is a torn reorder
        width = int(beam.get("width") or 0)
        live = ds.get("live") or {}
        slot_pages = ds.get("slot_pages") or {}
        seen = set()
        for lane, b in sorted((beam.get("lanes") or {}).items()):
            slots = [int(x) for x in b.get("slots", [])]
            if len(slots) != width or any(
                    s // width != int(lane) for s in slots):
                problems.append(
                    "beam lane %s slots %s are not %d aligned "
                    "hypotheses of that lane" % (lane, slots, width))
            for s in slots:
                if s in seen:
                    problems.append(
                        "slot %d bound to two beam lanes" % s)
                seen.add(s)
                if str(s) not in live:
                    problems.append(
                        "beam lane %s binds slot %d which is not "
                        "live" % (lane, s))
                if str(s) not in slot_pages:
                    problems.append(
                        "beam lane %s binds slot %d with no page "
                        "list — its refcounts are unaccounted"
                        % (lane, s))
        lanes_total = (int((ds.get("config") or {})
                           .get("num_slots", 0)) // width
                       if width else 0)
        if (width and len(beam.get("lanes") or {})
                + len(beam.get("free_lanes") or []) != lanes_total):
            problems.append(
                "beam lane conservation broken: %d live + %d free != "
                "%d lanes" % (len(beam.get("lanes") or {}),
                              len(beam.get("free_lanes") or []),
                              lanes_total))
    return problems


def _serial_dirs(root):
    out = []
    for d in sorted(os.listdir(root)):
        if not d.startswith("checkpoint_"):
            continue
        suffix = d[len("checkpoint_"):]
        if suffix.isdigit():
            out.append((int(suffix), os.path.join(root, d)))
    return sorted(out)


def _summarize(step_dir, manifest, verify):
    vars_meta = manifest.get("vars", {})
    sharding = (manifest.get("extra") or {}).get("sharding")
    info = {
        "dir": step_dir,
        "manifest_version": manifest.get("manifest_version"),
        "serial": manifest.get("serial"),
        "step": manifest.get("step"),
        "num_vars": len(vars_meta) or len(manifest.get("files", [])),
        "bytes": sum(v.get("bytes", 0) for v in vars_meta.values()),
        "rng": manifest.get("rng"),
        "has_digests": any(
            v.get("sha256") or any(s.get("sha256")
                                   for s in v.get("shards", []))
            for v in vars_meta.values()),
        # the elastic dialect (elastic/reshard.py): which mesh this
        # checkpoint was written under and which vars are shard files
        "sharding": sharding,
        "sharded_vars": sorted(n for n, v in vars_meta.items()
                               if v.get("shards")),
    }
    # the decode-snapshot dialect (serving/snapshot.py): a live
    # SlotDecodeSession image — slots/pages/refcounts/prefix trie
    decode = (manifest.get("extra") or {}).get("decode_snapshot")
    info["decode"] = _decode_summary(decode) if decode else None
    if verify:
        problems = _verify(step_dir, manifest)
        if decode:
            problems = problems + _decode_verify(decode, vars_meta)
        info["problems"] = problems
    else:
        info["problems"] = None
    return info


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="checkpoint_<n> dir or checkpoint root")
    ap.add_argument("--verify", action="store_true",
                    help="re-hash every var file against the manifest")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable output")
    args = ap.parse_args(argv)

    if not os.path.isdir(args.path):
        print("ckpt_inspect: not a directory: %s" % args.path,
              file=sys.stderr)
        return 1
    manifest = _read_manifest(args.path)
    if manifest is not None:
        targets = [(manifest.get("serial"), args.path)]
    else:
        targets = [(s, d) for s, d in _serial_dirs(args.path)
                   if _read_manifest(d) is not None]
        if not targets:
            print("ckpt_inspect: no complete checkpoint under %s "
                  "(no readable %s)" % (args.path, MANIFEST_NAME),
                  file=sys.stderr)
            return 2
    rc = 0
    reports = []
    for serial, step_dir in targets:
        m = _read_manifest(step_dir)
        info = _summarize(step_dir, m, args.verify)
        reports.append(info)
        if info["problems"]:
            rc = 2
    if args.as_json:
        print(json.dumps(reports, indent=2, sort_keys=True))
    else:
        for info in reports:
            print("checkpoint serial=%s step=%s  vars=%d  %.1f MiB  "
                  "manifest v%s%s" % (
                      info["serial"], info["step"], info["num_vars"],
                      info["bytes"] / 1048576.0,
                      info["manifest_version"],
                      "  rng=%(base_seed)d@%(run_counter)d"
                      % info["rng"] if info["rng"] else ""))
            decode = info.get("decode")
            if decode:
                cfg = decode.get("config") or {}
                print("  decode snapshot: step %s  slots live=%s "
                      "free=%d/%d" % (
                          decode["steps_done"],
                          decode["live_slots"], decode["free_slots"],
                          cfg.get("num_slots", 0)))
                print("  pages: %d allocated (%d shared) / %d free of "
                      "%s;  reserved=%s leaked=%s" % (
                          decode["pages_allocated"],
                          decode["pages_shared"], decode["pages_free"],
                          cfg.get("num_pages"),
                          decode["reserved_pages"],
                          decode["leaked_pages"]))
                print("  prefix trie: %d entries;  pending requests: %d"
                      % (decode["prefix_entries"],
                         decode["pending_requests"]))
                spec = decode.get("speculative")
                if spec:
                    scfg = spec.get("config") or {}
                    rate = spec.get("acceptance_rate")
                    print("  speculative: k=%s drafter=%s  proposed=%d "
                          "accepted=%d (%s)  dispatches=%d  draft "
                          "cache slots=%d  draft params=%d" % (
                              scfg.get("k"), spec.get("drafter")
                              or scfg.get("drafter"),
                              spec["proposed"], spec["accepted"],
                              "%.2f accept" % rate
                              if rate is not None else "no proposals",
                              spec["dispatches"],
                              spec["draft_cached_slots"],
                              spec["draft_params"]))
                beam = decode.get("beam")
                if beam:
                    print("  beam: width=%s  lanes live=%d free=%d  "
                          "banked n-bests=%d" % (
                              beam["width"], len(beam["lanes"]),
                              beam["free_lanes"],
                              beam["banked_results"]))
                    for lane, b in sorted(beam["lanes"].items()):
                        print("    lane %s: slots=%s scores=%s "
                              "done=%s parents=%s" % (
                                  lane, b["slots"],
                                  ["%.3f" % s if s is not None
                                   else "?" for s in b["scores"]],
                                  b["done"], b["last_parents"]))
            sharding = info.get("sharding")
            if sharding:
                mesh = sharding.get("mesh_axes") or {}
                factors = sharding.get("factors") or {}
                print("  mesh: %s" % (" x ".join(
                    "%s=%d" % (a, mesh[a]) for a in sorted(mesh))
                    or "(unrecorded)"))
                print("  shard factors: %s" % (", ".join(
                    "%s/%d" % (n, factors[n]) for n in sorted(factors))
                    or "(all vars whole)"))
            if args.verify:
                if info["problems"]:
                    for p in info["problems"]:
                        print("  FAIL %s" % p)
                elif info["has_digests"]:
                    print("  verified: all digests match")
                else:
                    print("  verified: files present (v1 manifest, "
                          "no digests)")
    return rc


if __name__ == "__main__":
    sys.exit(main())
