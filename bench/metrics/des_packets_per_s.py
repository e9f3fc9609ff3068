"""Packets the DES delivered per second of the window's wall time
(``repro.net.simcore.PERF.packets``: one per packet, also when the DES
coalesces several into one event)."""


def read(mi):
    packets = mi.work.get("packets")
    if not packets:
        return None
    return packets / mi.work["wall_s"]
