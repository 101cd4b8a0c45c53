"""Operations the window's processed tokens need (``work.py``) over the
window's seconds x chips x the chip's peak (model step, whole, on the
four chips of the tensor-parallel mesh)."""


def read(ctx):
    return ctx.mfu()
