"""The persistent AOT compiled-program bank (ROADMAP item 3).

One tally = one mesh = one freshly-jitted program means every new
server process pays the full XLA compile cost of the walk and megastep
programs before it can serve a single request.  This module removes
that cost: the two program families a served job dispatches — the
packed walk step (``ops/walk.py trace_packed``, which also carries the
initial-location search) and the fused device-sourced move loop
(``megastep``) — are lowered, compiled, SERIALIZED
(``jax.experimental.serialize_executable``) and written to a disk bank,
so a fresh server process deserializes executables instead of
recompiling them: ZERO XLA compiles of the program families in steady
state (pinned by a fresh-subprocess test in tests/test_serving.py).

Layout — one directory per environment section, exactly the
``{backend, x64, n_devices}`` sectioning TUNING.json uses (a CPU-built
executable means nothing to a TPU process, and vice versa)::

  <root>/<env key e.g. cpu-x64off-d1>/<family>-<signature hash>/
      PROGRAM.bin   the serialized executable (PjRt bytes)
      META.json     schema, pinned environment, family, statics,
                    dynamic-arg signature, lowered-HLO sha256,
                    donated-argument count, shape-class key,
                    compile seconds, program sha256

The entry key hashes the dynamic-argument signature (shape/dtype of
every pytree leaf plus the tree structure) and the full static-kwarg
set — the same inputs that key the jit cache — so a program is reused
exactly where the jit path would reuse its compiled entry.  The
in/out pytree structure an executable needs at load time is NOT
persisted: a fresh ``.trace(...).lower()`` of the same call (pure
tracing, no compile, sub-second) reconstructs it, and doubles as the
staleness probe — the trace's lowered-HLO sha256 must match the one
recorded at compile time, so an entry built by older code is
recompiled instead of silently serving a stale program.

Load-time validation (the PR 9 finding, resolved)
-------------------------------------------------
analysis/costmodel.py:145 documents that executables DESERIALIZED from
a cache report an EMPTY aliasing plan (``memory_analysis().alias_size
_in_bytes == 0``) — which is why the cost contracts bypass the
persistent compile cache.  The bank cannot bypass itself, so every
loaded executable is re-validated against the donation + 1+1-transfer
contract at load time, against the compiled HLO TEXT (which, unlike
``memory_analysis``, survives the round trip: ``input_output_alias``
and any host-callback custom-calls are module attributes):

  * ``cost.donation.aot``  the aliasing plan must still cover at least
    one output (the donated flux accumulator).  A serialized executable
    that lost its donation doubles accumulator HBM and breaks the
    facade's re-arm contract.
  * ``cost.io.aot``        no host-callback custom-call targets — a
    callback is a hidden per-dispatch host sync that would silently
    turn the 1+1 transfer contract into 1+1+N.

Any mismatch (or a lowered-HLO staleness mismatch) RECOMPILES the
program and REWRITES the cache entry, counted in
``pumi_aot_rewrites_total{cause=...}`` and recorded as a named Finding
on ``bank.findings``.  The same validator runs as graft-check layer 3's
``cost.donation.aot`` gate (analysis/costmodel.check_aot), so the AOT
path is provably as donated as the jit path on every CI run.

Programs that cannot serialize (e.g. a Pallas interpret-mode body)
fall back to the jit path for the lifetime of the process — the bank
degrades to today's behavior, never blocks a dispatch.
"""
from __future__ import annotations

import hashlib
import json
import os
import re
import threading
import time
from typing import Callable, NamedTuple

BANK_SCHEMA = 1
PROGRAM_FILE = "PROGRAM.bin"
META_FILE = "META.json"

# Fault hook (tests/test_serving.py): compile the next bank entry
# WITHOUT donated arguments, so the written executable genuinely lost
# its aliasing plan — the load-time validator must then name
# cost.donation.aot, recompile, and rewrite the entry.
ENV_FAULT = "PUMI_TPU_AOT_FAULT"


def environment() -> dict:
    """The pinned bank environment — the same contract as the tuning
    database and the contract captures."""
    from ..analysis.contracts import environment as _env

    return _env()


def section_key(env: dict | None = None) -> str:
    from ..tuning.db import env_key

    return env_key(env or environment())


class _Family(NamedTuple):
    """One bankable program family: its production jit wrapper, the
    plain-jit fallback for unbankable programs, where the donated flux
    sits in the positional args, and which kwargs are DYNAMIC arrays
    (everything else in the call's kwargs is a static)."""

    name: str
    jit: object
    fallback: Callable
    impl: Callable
    flux_index: int
    dyn_kwargs: tuple


def _families() -> dict:
    import inspect

    from ..ops import walk

    # Flux positions derived from the impl signatures (the same idiom
    # walk.py uses for its own wrappers) so a reordered/inserted
    # parameter breaks loudly here instead of silently resolving
    # tally_scatter='auto' against the wrong argument.
    mega_flux = list(
        inspect.signature(walk.megastep_impl).parameters
    ).index("flux")
    return {
        "trace_packed": _Family(
            "trace_packed", walk._trace_packed_jit, walk.trace_packed,
            walk.trace_packed_impl, walk._PACKED_FLUX_ARG_INDEX,
            ("weight", "group", "conv_state", "seed_grid"),
        ),
        "megastep": _Family(
            "megastep", walk._megastep_jit, walk.megastep,
            walk.megastep_impl, mega_flux, (),
        ),
    }


# --------------------------------------------------------------------- #
# Entry keying
# --------------------------------------------------------------------- #
def _leaf_sig(x) -> str:
    import numpy as np

    dt = getattr(x, "dtype", None)
    if dt is None:
        return repr(x)
    shape = ",".join(map(str, getattr(x, "shape", ())))
    return f"{np.dtype(dt).name}[{shape}]"


def call_signature(args: tuple, dyn_kwargs: dict) -> list[str]:
    """Shape/dtype signature of every dynamic leaf plus the pytree
    structure — what distinguishes one compiled entry from another on
    the dynamic side (mirrors the jit cache key's aval component)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, dyn_kwargs))
    return [_leaf_sig(x) for x in leaves] + [str(treedef)]


def canonical_statics(statics: dict) -> dict:
    """Static kwargs as stable strings (floats repr round-trip;
    tuples/None repr deterministically) for hashing and META."""
    return {k: repr(v) for k, v in sorted(statics.items())}


def entry_key(family: str, args: tuple, dyn_kwargs: dict,
              statics: dict) -> str:
    payload = json.dumps(
        {
            "schema": BANK_SCHEMA,
            "family": family,
            "signature": call_signature(args, dyn_kwargs),
            "statics": canonical_statics(statics),
        },
        sort_keys=True,
    )
    h = hashlib.sha256(payload.encode()).hexdigest()[:20]
    return f"{family}-{h}"


def _arg_devices(tree) -> list:
    """The devices a dispatch of ``tree`` runs on: those its arrays are
    committed to, else the default device. A loaded executable must
    name them; by default JAX loads it onto every device of the host,
    which no single-device call can feed."""
    import jax

    devices = {
        d
        for x in jax.tree_util.tree_leaves(tree)
        if isinstance(x, jax.Array)
        for d in x.devices()
    }
    return sorted(devices, key=lambda d: d.id) or [jax.devices()[0]]


# --------------------------------------------------------------------- #
# Load-time validation (the compiled half of the donation/1+1 contract)
# --------------------------------------------------------------------- #
_ALIAS_MARKS = ("may-alias", "must-alias")
_CALLBACK_RE = re.compile(r'custom_call_target\s*=\s*"([^"]*callback[^"]*)"')


def alias_marks(compiled) -> int:
    """Number of aliased (donated) entries in one executable's
    compiled-HLO ``input_output_alias`` plan — the compile-time
    expectation the load-time validator compares against."""
    txt = compiled.as_text()
    return sum(txt.count(m) for m in _ALIAS_MARKS)


def validate_loaded(
    compiled, family: str = "", *, expect_alias: int | None = None
) -> list[tuple[str, str]]:
    """Validate one LOADED executable against the donation +
    1+1-transfer contract.  Returns ``[(symbol, message), ...]`` —
    empty means the executable is as donated and as transfer-free as a
    fresh compile.  Checked on the compiled HLO text, which survives
    serialization (``memory_analysis`` does not — the PR 9 finding this
    validator exists to close).

    ``expect_alias`` is the alias-entry count of the FRESH compile
    (recorded in META.json at write time); the loaded plan must match
    it exactly — a PARTIAL drop (e.g. flux kept but the convergence /
    batch-squares accumulators lost) is the same named finding as a
    total one.  Without it, at least one alias entry (the donated
    flux) is still required."""
    tag = f" ({family})" if family else ""
    try:
        txt = compiled.as_text()
    except Exception as e:  # pragma: no cover - backend-specific
        return [(
            "cost.donation.aot",
            f"loaded executable{tag} exposes no HLO text to validate "
            f"the aliasing plan against ({e}) — treat as a dropped "
            "donation and recompile",
        )]
    out: list[tuple[str, str]] = []
    n_alias = sum(txt.count(m) for m in _ALIAS_MARKS)
    if "input_output_alias" not in txt or n_alias < 1:
        out.append((
            "cost.donation.aot",
            f"loaded executable{tag} carries no input_output_alias "
            "entry — the flux donation was dropped in serialization; "
            "peak memory grows by one accumulator and the re-arm "
            "contract breaks",
        ))
    elif expect_alias is not None and n_alias != expect_alias:
        out.append((
            "cost.donation.aot",
            f"loaded executable{tag} carries {n_alias} aliased "
            f"entr{'y' if n_alias == 1 else 'ies'} but the fresh "
            f"compile recorded {expect_alias} — a PARTIAL donation "
            "drop (e.g. the convergence/batch-squares accumulators) "
            "grows peak memory per resident job",
        ))
    callbacks = _CALLBACK_RE.findall(txt)
    if callbacks:
        out.append((
            "cost.io.aot",
            f"loaded executable{tag} contains host-callback custom-"
            f"call(s) {sorted(set(callbacks))} — a hidden per-dispatch "
            "host sync; the 1+1 transfer contract does not survive it",
        ))
    return out


class _Program(NamedTuple):
    """One resolved bank program: the loaded/compiled executable (None
    = unbankable this process, dispatch falls back to the jit path) and
    its provenance tag for telemetry ("hit", "miss", a rewrite cause
    — "stale" / "corrupt" / "invalid" — or "unbankable")."""

    compiled: object | None
    provenance: str


# --------------------------------------------------------------------- #
# The bank
# --------------------------------------------------------------------- #
class ProgramBank:
    """Disk-backed AOT executable cache for the serving program
    families.  Attach to a facade via ``PumiTally(...,
    program_bank=bank)``; the facade then routes its packed-walk and
    megastep dispatches through :meth:`dispatch`."""

    def __init__(self, root: str, *, registry=None, recorder=None,
                 tracer=None):
        from ..obs import FlightRecorder, MetricsRegistry, SpanTracer

        self.root = str(root)
        self.env = environment()
        self.section = section_key(self.env)
        self.section_dir = os.path.join(self.root, self.section)
        self.registry = (
            registry if registry is not None else MetricsRegistry()
        )
        self.recorder = (
            recorder if recorder is not None else FlightRecorder()
        )
        # Span tracer (obs/trace.py): the scheduler passes its own so
        # resolve/deserialize/compile spans land in the CURRENT job's
        # trace via the ambient binding; a standalone bank gets a
        # private (ring-only) tracer.
        self.tracer = tracer if tracer is not None else SpanTracer()
        r = self.registry
        self._hits = r.counter(
            "pumi_aot_hits_total",
            "program-bank dispatches served from a deserialized "
            "AOT executable (no XLA compile)",
        )
        self._misses = r.counter(
            "pumi_aot_misses_total",
            "program-bank dispatches that compiled (entry absent, "
            "stale, or invalid)",
        )
        self._compile_s = r.counter(
            "pumi_compile_seconds_total",
            "wall seconds spent in XLA compilation by the program bank",
        )
        self._rewrites = r.counter(
            "pumi_aot_rewrites_total",
            "bank entries recompiled and rewritten after load-time "
            "validation (labeled by cause: donation, io, stale, "
            "corrupt)",
        )
        self._lock = threading.Lock()
        # In-memory programs resolved this process, keyed by entry key.
        self._programs: dict[str, _Program] = {}
        # Load-time validation findings (analysis.Finding objects) —
        # the test/introspection surface mirroring the cost.donation.aot
        # lint gate.
        self.findings: list = []

    # -- counter views (the bench/scheduler summary surface) ----------- #
    @property
    def hits(self) -> int:
        return int(self._hits.value())

    @property
    def misses(self) -> int:
        return int(self._misses.value())

    @property
    def rewrites(self) -> int:
        seen = self._rewrites.snapshot()["series"]
        return int(sum(s["value"] for s in seen))

    @property
    def compile_seconds(self) -> float:
        return float(self._compile_s.value())

    def stats(self) -> dict:
        return {
            "root": self.root,
            "section": self.section,
            "hits": self.hits,
            "misses": self.misses,
            "rewrites": self.rewrites,
            "compile_seconds": round(self.compile_seconds, 3),
            "entries": len(self._programs),
        }

    # ------------------------------------------------------------------ #
    def dispatch(self, family: str, args: tuple, kwargs: dict, *,
                 shape_key: str | None = None):
        """Run one facade dispatch through the bank: resolve the entry
        (load-or-compile on first use per process), then call the
        executable with the dynamic arguments only (statics are baked
        into the compiled program).  Unbankable programs fall back to
        the production jit wrapper — same results, jit-cache compile
        cost."""
        fam = _families()[family]
        kwargs = dict(kwargs)
        if kwargs.get("tally_scatter", "auto") == "auto":
            # Resolve exactly like the jit wrappers do, BEFORE the
            # entry key forms — "auto" is not a compilable static.
            from ..ops.walk import resolve_tally_scatter

            kwargs["tally_scatter"] = resolve_tally_scatter(
                "auto", args[fam.flux_index]
            )
        dyn = {k: kwargs.pop(k) for k in fam.dyn_kwargs if k in kwargs}
        statics = kwargs
        # The steady-state memo key: leaf shapes/dtypes + tree
        # structure + the statics themselves (hashable by definition —
        # they are jit statics).  Everything the disk entry key hashes,
        # but as a plain tuple lookup — no json/sha256 on the per-move
        # hot path; the hex entry key is derived only on first
        # resolution (_acquire).
        import jax

        leaves, treedef = jax.tree_util.tree_flatten((args, dyn))
        memo_key = (
            family,
            treedef,
            tuple(
                (getattr(x, "shape", None), str(getattr(x, "dtype", x)))
                for x in leaves
            ),
            tuple(sorted(statics.items(), key=lambda kv: kv[0])),
        )
        with self._lock:
            prog = self._programs.get(memo_key)
        if prog is None:
            prog = self._acquire(
                fam, memo_key, args, dyn, statics, shape_key
            )
        if prog.compiled is None:
            return fam.fallback(*args, **dyn, **statics)
        return prog.compiled(*args, **dyn)

    # ------------------------------------------------------------------ #
    def _acquire(self, fam, memo_key, args, dyn, statics, shape_key):
        """Resolve one entry: fresh trace+lower (pure — reconstructs
        the pytree metadata and the staleness hash), then load+validate
        from disk or compile+serialize+write."""
        import jax

        key = entry_key(fam.name, args, dyn, statics)
        with self.tracer.span(
            "aot_resolve", family=fam.name, key=key
        ) as sp:
            prog = self._acquire_inner(
                fam, memo_key, args, dyn, statics, shape_key, key
            )
            sp["outcome"] = prog.provenance
        return prog

    def _acquire_inner(self, fam, memo_key, args, dyn, statics,
                       shape_key, key):
        import jax

        traced = fam.jit.trace(*args, **dyn, **statics)
        lowered = traced.lower()
        in_tree = jax.tree_util.tree_flatten(lowered.args_info)[1]
        out_tree = lowered.out_tree
        hlo_sha = hashlib.sha256(lowered.as_text().encode()).hexdigest()
        entry_dir = os.path.join(self.section_dir, key)
        meta_path = os.path.join(entry_dir, META_FILE)
        prog_path = os.path.join(entry_dir, PROGRAM_FILE)

        compiled, provenance = None, "miss"
        loaded = self._try_load(
            fam, key, meta_path, prog_path, in_tree, out_tree, hlo_sha,
            _arg_devices((args, dyn)),
        )
        if loaded is not None:
            compiled, provenance = loaded
        if compiled is None:
            if provenance == "miss":
                self._misses.inc()
            compiled = self._compile_and_write(
                fam, key, lowered, entry_dir, hlo_sha, args, dyn,
                statics, shape_key,
            )
            if compiled is None:
                prog = _Program(None, "unbankable")
                with self._lock:
                    self._programs[memo_key] = prog
                return prog
        prog = _Program(compiled, provenance)
        with self._lock:
            self._programs[memo_key] = prog
        self.recorder.record(
            "aot", family=fam.name, key=key, outcome=provenance,
            shape_key=shape_key, job_id=self.tracer.current[1],
        )
        return prog

    def _try_load(self, fam, key, meta_path, prog_path, in_tree,
                  out_tree, hlo_sha, devices):
        """Load one disk entry onto ``devices``.  Returns
        ``(compiled, "hit")`` on a clean validated load,
        ``(None, "<cause>")`` when the entry exists but must be
        rewritten (counted), or None on a plain miss."""
        if not (os.path.exists(meta_path) and os.path.exists(prog_path)):
            return None
        from jax.experimental.serialize_executable import (
            deserialize_and_load,
        )

        try:
            with open(meta_path) as fh:
                meta = json.load(fh)
            with open(prog_path, "rb") as fh:
                payload = fh.read()
        except (OSError, ValueError) as e:
            # ValueError covers json.JSONDecodeError AND the
            # UnicodeDecodeError a byte-flipped META raises before the
            # json parser even runs — a torn/corrupted entry must
            # degrade to a recompile-and-rewrite, never crash a
            # dispatch.
            self._note_rewrite(fam, key, "corrupt", f"unreadable: {e}")
            return (None, "corrupt")
        if not isinstance(meta, dict):
            self._note_rewrite(
                fam, key, "corrupt",
                f"META.json parses but is not an object: {type(meta).__name__}",
            )
            return (None, "corrupt")
        if (
            meta.get("schema") != BANK_SCHEMA
            or meta.get("environment") != self.env
        ):
            self._note_rewrite(
                fam, key, "stale",
                f"schema/environment mismatch (entry: "
                f"{meta.get('schema')}/{meta.get('environment')}, "
                f"bank: {BANK_SCHEMA}/{self.env})",
            )
            return (None, "stale")
        if meta.get("sha256") != hashlib.sha256(payload).hexdigest():
            self._note_rewrite(
                fam, key, "corrupt", "program bytes fail their digest"
            )
            return (None, "corrupt")
        if meta.get("hlo_sha256") != hlo_sha:
            # The code that traces this call today lowers a DIFFERENT
            # program than the one that was compiled — an entry from an
            # older build must never serve stale semantics.
            self._note_rewrite(
                fam, key, "stale",
                "lowered-HLO hash drifted since the entry was compiled",
            )
            return (None, "stale")
        try:
            with self.tracer.span(
                "aot_deserialize", family=fam.name, key=key,
                bytes=len(payload),
            ):
                compiled = deserialize_and_load(
                    payload, in_tree, out_tree,
                    execution_devices=devices,
                )
        except Exception as e:
            self._note_rewrite(
                fam, key, "corrupt", f"deserialization failed: {e}"
            )
            return (None, "corrupt")
        problems = validate_loaded(
            compiled, fam.name, expect_alias=meta.get("alias_marks")
        )
        if problems:
            for symbol, message in problems:
                self._note_rewrite(
                    fam, key,
                    "donation" if symbol == "cost.donation.aot" else "io",
                    message, symbol=symbol,
                )
            return (None, "invalid")
        self._hits.inc()
        return (compiled, "hit")

    def _note_rewrite(self, fam, key, cause, message, *,
                      symbol=None) -> None:
        from ..analysis import Finding
        from ..utils.log import log_warn

        self._rewrites.inc(cause=cause)
        self.findings.append(
            Finding(
                rule="COST",
                path=os.path.join(self.section, key),
                line=0,
                symbol=symbol or f"aot.{cause}",
                message=f"[{fam.name}] {message}",
            )
        )
        self.recorder.record(
            "aot_rewrite", family=fam.name, key=key, cause=cause,
            message=message, job_id=self.tracer.current[1],
        )
        log_warn(
            f"program bank: rewriting entry {key} ({cause}): {message}"
        )

    # ------------------------------------------------------------------ #
    def _compile_and_write(self, fam, key, lowered, entry_dir, hlo_sha,
                           args, dyn, statics, shape_key):
        """Compile (persistent compile cache bypassed — a cache-served
        executable would record the cache's provenance, not a fresh
        compile's, and its reported aliasing plan is exactly the PR 9
        artifact this bank validates against), serialize, and write the
        entry atomically.  Returns the compiled program, or None when
        the family cannot compile at all (never expected — compile
        errors propagate)."""
        import jax
        from jax.experimental.serialize_executable import serialize

        from ..analysis.costmodel import fresh_compile

        t0 = time.perf_counter()
        if os.environ.get(ENV_FAULT, "") == "drop_donation":
            # Fault hook: an UNDONATED twin of the same program — same
            # statics, same trees, no aliasing plan — so the written
            # entry reproduces a genuine donation drop for the
            # load-time validator to catch.
            twin = jax.jit(fam.impl, static_argnames=tuple(statics))
            lowered = twin.trace(*args, **dyn, **statics).lower()
        with self.tracer.span("aot_compile", family=fam.name, key=key):
            compiled = fresh_compile(lowered)
        dt = time.perf_counter() - t0
        self._compile_s.inc(dt)
        try:
            payload, _, _ = serialize(compiled)
        except (ValueError, TypeError) as e:
            from ..utils.log import log_warn

            log_warn(
                f"program bank: {fam.name} entry {key} is not "
                f"serializable ({e}); serving it from the jit path "
                "this process"
            )
            return None
        donated = sum(
            lowered.as_text().count(m)
            for m in ("tf.aliasing_output", "jax.buffer_donor")
        )
        meta = {
            "schema": BANK_SCHEMA,
            "environment": self.env,
            "family": fam.name,
            "key": key,
            "shape_key": shape_key,
            "signature": call_signature(args, dyn),
            "statics": canonical_statics(statics),
            "hlo_sha256": hlo_sha,
            "donated": donated,
            # Compiled-plan alias entries, the load-time validator's
            # exact expectation: a PARTIAL donation drop in a future
            # serialization change must not hide behind the flux alias.
            "alias_marks": alias_marks(compiled),
            "compile_seconds": round(dt, 3),
            "sha256": hashlib.sha256(payload).hexdigest(),
        }
        self._write_entry(entry_dir, payload, meta)
        return compiled

    @staticmethod
    def _write_entry(entry_dir: str, payload: bytes, meta: dict) -> None:
        """Atomic entry write: bytes first, META last (an entry without
        META is invisible — the two-phase discipline the checkpoint
        store established)."""
        os.makedirs(entry_dir, exist_ok=True)
        for name, data in (
            (PROGRAM_FILE, payload),
            (META_FILE, (json.dumps(meta, indent=1, sort_keys=True)
                         + "\n").encode()),
        ):
            tmp = os.path.join(entry_dir, name + ".tmp")
            with open(tmp, "wb") as fh:
                fh.write(data)
                fh.flush()
                os.fsync(fh.fileno())
            os.replace(tmp, os.path.join(entry_dir, name))

    # ------------------------------------------------------------------ #
    def memory_analysis(self) -> dict:
        """HBM footprint over every program RESOLVED for dispatch so
        far (the fleet profiler's high-water source).  Per executable
        the footprint is argument + output + temp bytes from XLA's
        ``memory_analysis()``; executables that expose none —
        deserialized entries report empty analyses (the PR 9 finding),
        and CPU backends may expose nothing at all — count as
        ``unanalyzed`` rather than as zero-byte programs."""
        high = 0
        analyzed = unanalyzed = 0
        with self._lock:
            programs = list(self._programs.values())
        for prog in programs:
            if prog.compiled is None:
                continue
            try:
                ma = prog.compiled.memory_analysis()
                footprint = int(
                    getattr(ma, "argument_size_in_bytes", 0)
                    + getattr(ma, "output_size_in_bytes", 0)
                    + getattr(ma, "temp_size_in_bytes", 0)
                )
            except Exception:
                unanalyzed += 1
                continue
            if footprint <= 0:
                unanalyzed += 1
                continue
            analyzed += 1
            high = max(high, footprint)
        return {
            "high_water_bytes": high,
            "analyzed": analyzed,
            "unanalyzed": unanalyzed,
        }

    def entries_on_disk(self) -> list[str]:
        """Committed entry keys in this environment's section."""
        if not os.path.isdir(self.section_dir):
            return []
        return sorted(
            d for d in os.listdir(self.section_dir)
            if os.path.exists(
                os.path.join(self.section_dir, d, META_FILE)
            )
        )
