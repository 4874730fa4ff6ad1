"""Machine models.

Three hardware models live here:

* :class:`TpuV5e` -- the TARGET device for the TPU-native adaptation of the
  paper's design rules.  All roofline terms in ``launch/roofline.py`` and all
  tiling-planner latency estimates in ``core/tiling.py`` read from this model.

* :class:`AieMl` -- the paper's AI-Engine machine model (VEK280, AIE-ML array),
  parameterized exactly as the paper describes it (Section IV-B).  Used by the
  paper-faithful reproduction of Figs. 2-7 / Table I.

* :class:`PlFabric` -- the paper's programmable-logic (HLS4ML) machine model:
  a reuse-factor-driven spatial dataflow cost model.  Used by the LARE metric
  (Alg. 1) and the Fig. 2/3 reproductions.

Every constant is a dataclass field so experiments can re-parameterize (e.g.
a different Versal part or a TPU v5p) without touching the algorithms.
"""

from __future__ import annotations

import dataclasses
import math

GiB = 1024**3
MiB = 1024**2
KiB = 1024


@dataclasses.dataclass(frozen=True)
class TpuV5e:
    """TPU v5e single-chip model + pod interconnect (assignment constants)."""

    # Compute.
    peak_bf16_flops: float = 197e12      # FLOP/s per chip (MXU, bf16)
    peak_int8_ops: float = 394e12        # OP/s per chip (int8)
    mxu: int = 128                       # systolic array dimension
    # Memory hierarchy.
    hbm_bytes: int = 16 * GiB
    hbm_bw: float = 819e9                # B/s per chip
    vmem_bytes: int = 128 * MiB          # on-chip vector memory
    vreg_lane: int = 128                 # lane count (last-dim tiling)
    vreg_sublane: int = 8                # sublanes for 4-byte types
    # Interconnect.
    ici_bw: float = 50e9                 # B/s per link (assignment constant)
    ici_links: int = 4                   # torus links per chip (2D torus, v5e)
    dcn_bw: float = 12.5e9               # B/s per chip cross-pod (est., documented)
    # Dispatch overhead charged per un-fused kernel boundary (seconds). This is
    # the fixed part of the paper's DR7 boundary-crossing cost on TPU.
    kernel_overhead_s: float = 2.2e-6
    # Cost of keeping a layer boundary INSIDE a fused megakernel: the epilogue
    # requantize (round/clip/cast through VMEM scratch) paid per fused inner
    # boundary instead of the full crossing.  The fuse-vs-split decision is
    # epilogue-vs-crossing; the characterization harness fits this from the
    # fused-chain sweep (``repro.characterize`` term ``fused_chain``).
    fused_epilogue_s: float = 3e-7

    def sublanes_for(self, itemsize: int) -> int:
        """Second-to-last-dim tiling multiple for a dtype of `itemsize` bytes."""
        return self.vreg_sublane * max(1, 4 // itemsize)

    def matmul_time(self, m: int, k: int, n: int, *, itemsize: int = 2) -> float:
        """Roofline time of one dense matmul on one chip (compute vs HBM)."""
        flops = 2.0 * m * k * n
        peak = self.peak_int8_ops if itemsize == 1 else self.peak_bf16_flops
        # MXU efficiency: padding waste when dims are not multiples of the MXU.
        eff = (
            min(1.0, m / _ceil_to(m, self.vreg_sublane))
            * min(1.0, k / _ceil_to(k, self.mxu))
            * min(1.0, n / _ceil_to(n, self.mxu))
        )
        t_compute = flops / (peak * max(eff, 1e-9))
        bytes_moved = itemsize * (m * k + k * n) + 4 * (m * n)
        t_memory = bytes_moved / self.hbm_bw
        return max(t_compute, t_memory)


@dataclasses.dataclass(frozen=True)
class AieMl:
    """AMD Versal VEK280 AIE-ML array model (paper Section IV-B constants)."""

    clock_hz: float = 1e9                # hardened, up to 1 GHz
    macs_per_cycle_int8: int = 256       # per compute tile
    tiles_total: int = 304               # 38 cols x 8 rows
    cols: int = 38
    rows: int = 8
    usable_cols: int = 31                # AIE4ML restriction (cols 7..37)
    local_mem_bytes: int = 64 * KiB      # per-tile data memory
    load_bw: float = 64e9                # B/s local read (2x256-bit @1GHz)
    store_bw: float = 32e9               # B/s local write (1x256-bit @1GHz)
    cascade_bits: int = 512              # west->east partial-sum bus
    stream_bits: int = 32                # per-tile in/out streaming ports
    plio_bw: float = 5e9                 # B/s (128-bit @ 312.5 MHz)
    dsp58_equiv_per_tile: float = 58.0   # paper: one tile ~ 58 DSP58s
    # Fig.-6 band-spill contention: fractional latency added per layer placed
    # in a spilled band.  A machine-model field (not a tiling-module constant)
    # so the characterization harness (repro.characterize) can substitute the
    # fitted slope and the plan key picks up the change.
    band2_penalty_per_layer: float = 0.085

    # Legal aie::mmul API tile shapes for i8 x i8 (paper Fig. 4 y-axis).
    legal_api_tiles_i8: tuple = (
        (4, 8, 4), (4, 8, 8), (4, 16, 4), (4, 16, 8), (8, 8, 4), (8, 8, 8),
    )

    # Empirical per-API-shape efficiency (fraction of peak MACs/cycle reached in
    # steady state), calibrated to reproduce Fig. 4's ordering: (4,8,8) and
    # (4,16,8) best; small-N shapes starve the wide accumulators.
    def api_efficiency(self, s_m: int, s_k: int, s_n: int) -> float:
        base = {
            (4, 8, 4): 0.52, (4, 8, 8): 0.95, (4, 16, 4): 0.55,
            (4, 16, 8): 0.93, (8, 8, 4): 0.60, (8, 8, 8): 0.82,
        }.get((s_m, s_k, s_n), 0.40)
        return base


@dataclasses.dataclass(frozen=True)
class PlFabric:
    """HLS4ML-on-PL spatial-dataflow model (VEK280 PL side, paper Section III).

    A dense layer (n_in, n_out) with reuse factor rf:
      * uses  ceil(n_in*n_out / rf) multipliers (DSP58s),
      * has initiation interval II ~= rf cycles (plus fixed pipeline depth),
      * stores all weights on-chip (BRAM under the Resource strategy, LUT/FF
        under the Latency strategy).
    """

    clock_hz: float = 312.5e6            # PL clock used in the paper
    dsp_total: int = 1312                # approximate VEK280 PL DSP58 budget
    lut_total: int = 900_000             # approximate; configurable
    bram_bits_total: int = 967 * 36 * 1024  # approximate 36kb BRAM blocks
    pipeline_depth: int = 12             # fixed pipeline fill latency (cycles)
    # The Latency strategy burns ~alpha LUTs per weight bit instead of BRAM.
    latency_strategy_lut_per_weight_bit: float = 1.1

    def legal_reuse_factors(self, n_in: int, n_out: int) -> list[int]:
        """HLS4ML legal rf values: divisors of n_in*n_out (capped)."""
        total = n_in * n_out
        rfs = [d for d in range(1, min(total, 4096) + 1) if total % d == 0]
        return rfs

    def dsps(self, n_in: int, n_out: int, rf: int) -> int:
        return math.ceil(n_in * n_out / rf)

    def interval_cycles(self, rf: int) -> int:
        return max(1, rf)

    def latency_s(self, n_in: int, n_out: int, rf: int, batch: int = 8) -> float:
        # Streaming batch through a pipelined datapath: fill + (batch-1)*II.
        cycles = self.pipeline_depth + math.ceil(math.log2(max(2, n_in))) \
            + (batch - 1) * self.interval_cycles(rf) + self.interval_cycles(rf)
        return cycles / self.clock_hz

    def interval_s(self, rf: int) -> float:
        return self.interval_cycles(rf) / self.clock_hz

    def resources(self, n_in: int, n_out: int, rf: int, *,
                  strategy: str = "resource", weight_bits: int = 8) -> dict:
        """Resource vector for one dense layer at a given reuse factor."""
        dsp = self.dsps(n_in, n_out, rf)
        w_bits = n_in * n_out * weight_bits
        if strategy == "latency":
            lut = int(w_bits * self.latency_strategy_lut_per_weight_bit) + 40 * dsp
            bram_bits = 0
        else:
            lut = 28 * dsp
            bram_bits = w_bits if rf > 1 else 0  # rf=1 keeps weights in fabric
        return {"dsp": dsp, "lut": lut, "bram_bits": bram_bits}

    def fits(self, res: dict) -> bool:
        return (res["dsp"] <= self.dsp_total and res["lut"] <= self.lut_total
                and res["bram_bits"] <= self.bram_bits_total)

    def resource_scalar(self, res: dict) -> float:
        """Single-number resource consumption: DSP-equivalents (paper's x-axis).

        LUT and BRAM contributions are folded in as fractional DSP-equivalents
        by budget share, so one scalar spans the three PL resource types.
        """
        return (res["dsp"]
                + res["lut"] / self.lut_total * self.dsp_total * 0.25
                + res["bram_bits"] / self.bram_bits_total * self.dsp_total * 0.25)


def _ceil_to(x: int, q: int) -> int:
    return ((x + q - 1) // q) * q


# Canonical singletons (experiments may construct their own).
TPU_V5E = TpuV5e()
AIE_ML = AieMl()
PL_FABRIC = PlFabric()

# Stock models of the chips the planner knows, keyed by the device kind JAX
# reports (``jax.devices()[0].device_kind``).
DEVICE_MODELS = {"TPU v5 lite": TPU_V5E}


def device_model(device) -> TpuV5e:
    """The stock machine model of a JAX ``device``.  A platform or device
    kind with no entry in :data:`DEVICE_MODELS` raises: planning a chip
    under another chip's constants would be silently wrong."""
    model = (DEVICE_MODELS.get(device.device_kind)
             if device.platform == "tpu" else None)
    if model is None:
        raise ValueError(
            f"no machine model for {device.platform} device kind "
            f"{device.device_kind!r} (known: {sorted(DEVICE_MODELS)})")
    return model
