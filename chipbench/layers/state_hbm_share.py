"""What the recurrent state of a serving cell's requests holds of device
memory: the most slots that held a request at once (the program's
``state_slots_peak`` on its ``iteration_ledger`` lines, sampled every
iteration as the block manager's held blocks are) times the bytes of one
slot's state (chipbench/arith_olmo_hybrid.py, from the configuration's
shapes: float32 ``S`` and the convolution tails of every linear layer),
over the device's limit. ``hbm_live_share`` counts weights and K/V blocks
and cannot see it; the two together are the chip's real fill. None from a
program whose ledger has no state counts."""

from chipbench import arith_olmo_hybrid as need


def read(o):
    peaks = [e["state_slots_peak"] for e in o.events
             if e.get("type") == "serve"
             and e.get("event") == "iteration_ledger"
             and e.get("state_slots_peak") is not None]
    if not o.memory_limit_bytes or not peaks:
        return None
    return (100.0 * max(peaks) * need.state_bytes_per_slot(o.cell.config)
            / o.memory_limit_bytes)
