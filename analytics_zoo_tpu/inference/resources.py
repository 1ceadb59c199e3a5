"""Device-memory and program accounting for serving (PR 15 tentpole).

HBM residency became the scarce resource the platform optimizes — PR 14
packs weights to int4/int8, PR 12 pins bucketed KV/state lane buffers,
PR 11 parks an AOT executable per program — but nothing MEASURED what is
actually resident.  ``ResourceLedger`` decomposes a deployment's device
memory into its three structural components, each derived from the same
source of truth the optimizing PR introduced:

- **weights** — ``quantize.weight_bytes`` over the model's live params
  (+ state) tree: every leaf at its STORED dtype, so an int4-quantized
  deployment reads ~8x below its float twin (the PR 14 structural claim,
  now a live gauge instead of a bench printout).  The generation
  scheduler's operand copies (``ContinuousBatcher.operand_bytes``: the
  matmul weights rounded once to the type the backend multiplies in, held
  BESIDE that tree) are resident weights too and count here.
- **kv_state** — the generation scheduler's committed lane buffers
  (``ContinuousBatcher.state_bytes()``): fixed ``(max_active, bucket)``
  buffers per lane, the exact allocation PR 12's bucket geometry pins.
- **executables** — AOT executable count + best-effort generated-code
  size from the PR 11 cache (``aot_stats`` / ``aot_memory_bytes``).

The ledger feeds three surfaces: ``serving_hbm_bytes{component=}``
gauges in the engine registry, the ``resources`` block of the health doc
(fleet-aggregated by ``serving/fleet.py``), and the per-program
execution counters keyed by warm-up-manifest entry — the input the
ROADMAP's multi-model serving needs before it can apportion HBM between
co-resident models.

Pure numpy + the quantize helpers: importable without touching a device.
"""

from __future__ import annotations

from typing import Dict, Optional


def _tree_bytes(tree) -> int:
    if not tree:
        return 0
    from analytics_zoo_tpu.inference.quantize import weight_bytes
    return int(weight_bytes(tree))


class ResourceLedger:
    """One deployment's device-memory decomposition.  ``doc()`` is cheap
    enough for every /healthz scrape: the weights component is cached per
    AOT epoch (the tree only changes when the program family does), the
    lane/executable reads are O(lanes + cached programs)."""

    COMPONENTS = ("weights", "kv_state", "executables")

    def __init__(self, model, batcher=None):
        self.model = model
        self.batcher = batcher
        self._weights_cache: Optional[tuple] = None   # (epoch, bytes)
        self._qbits_cache: Optional[tuple] = None     # (epoch, bits)
        # executables only change when a program compiles: key the
        # best-effort memory_analysis sweep by (epoch, cached count) so
        # a steady-state scrape never re-walks the backend per program
        self._code_cache: Optional[tuple] = None      # (epoch, n, bytes)

    # -- components ----------------------------------------------------------
    def weights_bytes(self) -> int:
        # + the scheduler's operand copies of the weights, held beside the
        # model's tree (0 where its programs read that tree itself; its
        # ``generation`` stats name them).  Read live: the form is made at
        # the first program, not at construction.
        return self._tree_weights_bytes() \
            + int(getattr(self.batcher, "operand_bytes", 0) or 0)

    def _tree_weights_bytes(self) -> int:
        epoch = getattr(self.model, "_aot_epoch", None)
        if self._weights_cache is not None \
                and self._weights_cache[0] == epoch:
            return self._weights_cache[1]
        try:
            n = _tree_bytes(getattr(self.model, "_params", None)) \
                + _tree_bytes(getattr(self.model, "_state", None))
        except Exception:  # noqa: BLE001 — bridge models, exotic leaves
            n = 0
        self._weights_cache = (epoch, n)
        return n

    def kv_state_bytes(self) -> int:
        if self.batcher is None:
            return 0
        try:
            return int(self.batcher.state_bytes())
        except Exception:  # noqa: BLE001 — mid-construction race
            return 0

    def kv_state_doc(self) -> Optional[Dict]:
        """The kv_state decomposition (PR 18): ``{lanes, paged_pool,
        scales, aux, total}`` from the scheduler, or None for a batcher
        without the breakdown (or no batcher at all)."""
        fn = getattr(self.batcher, "state_bytes_doc", None)
        if not callable(fn):
            return None
        try:
            return dict(fn())
        except Exception:  # noqa: BLE001 — mid-construction race
            return None

    def executables(self) -> Dict:
        stats = {"count": 0, "code_bytes": None, "programs": {}}
        aot_stats = getattr(self.model, "aot_stats", None)
        if callable(aot_stats):
            try:
                s = aot_stats()
                stats["count"] = int(s.get("cached_programs", 0))
                stats["programs"] = dict(s.get("programs") or {})
            except Exception:  # noqa: BLE001
                pass
        mem = getattr(self.model, "aot_memory_bytes", None)
        if callable(mem):
            epoch = getattr(self.model, "_aot_epoch", None)
            key = (epoch, stats["count"])
            if self._code_cache is not None \
                    and self._code_cache[:2] == key:
                stats["code_bytes"] = self._code_cache[2]
            else:
                try:
                    stats["code_bytes"] = mem()
                except Exception:  # noqa: BLE001
                    stats["code_bytes"] = None
                self._code_cache = key + (stats["code_bytes"],)
        if self.batcher is not None:
            # the scheduler's compiled program set (prefill/insert/decode)
            # rides the same accounting, keyed by its own program names
            try:
                gs = self.batcher.program_stats()
                stats["count"] += int(gs.get("count", 0))
                stats["programs"].update(gs.get("programs") or {})
            except Exception:  # noqa: BLE001
                pass
        return stats

    # -- surfaces ------------------------------------------------------------
    def doc(self) -> Dict:
        """The health-doc ``resources`` block."""
        w = self.weights_bytes()
        kv = self.kv_state_bytes()
        exes = self.executables()
        code = exes.get("code_bytes")
        out = {
            "weights_bytes": w,
            "kv_state_bytes": kv,
            "executables": exes,
            "total_bytes": w + kv + (code or 0),
        }
        kvd = self.kv_state_doc()
        if kvd is not None:
            out["kv_state"] = kvd
        # cached per epoch like weights: quantized_bits flattens the
        # whole params tree, and this runs on every /healthz scrape
        epoch = getattr(self.model, "_aot_epoch", None)
        if self._qbits_cache is None or self._qbits_cache[0] != epoch:
            qbits = None
            try:
                from analytics_zoo_tpu.inference.quantize import (
                    quantized_bits)
                qbits = quantized_bits(getattr(self.model, "_params",
                                               None) or {})
            except Exception:  # noqa: BLE001
                pass
            self._qbits_cache = (epoch, qbits)
        if self._qbits_cache[1] is not None:
            out["quantized_bits"] = self._qbits_cache[1]
        return out

    def hbm_bytes(self, component: str) -> float:
        """Gauge provider for ``serving_hbm_bytes{component=}``."""
        if component == "weights":
            return float(self.weights_bytes())
        if component == "kv_state":
            return float(self.kv_state_bytes())
        if component == "executables":
            return float(self.executables().get("code_bytes") or 0)
        return 0.0
