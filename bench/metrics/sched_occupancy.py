"""Serve scheduler occupancy over the window: the share of batch slots
busy (decoding or prefilling) per decode step, from
``ServeScheduler.occupied_slot_steps`` and ``n_steps`` read at the window's
opening and close."""


def read(cell):
    occ = cell.data.get("occupancy")
    return None if occ is None else occ * 100.0
