"""The bytes each device all-reduced in the window (the program's counter
``mesh.psum_bytes``) over the collectives' device seconds, in GB/s.  None
where the program keeps no such counter."""


def read(ctx):
    moved = ctx["window"].get("mesh.psum_bytes")
    collective_s = ctx["trace"].get("collective_s", 0.0)
    if not moved or collective_s <= 0:
        return None
    return moved / collective_s / 1e9
