"""The whole forward's share of the chip's peak: the configuration's
operations per image times the images per second of the run's measured
window, over the chips times the peak for the operand type."""


def read(ctx):
    if ctx.flops_per_image <= 0 or ctx.images_per_s <= 0:
        return None
    return 100.0 * ctx.flops_per_image * ctx.images_per_s / (ctx.chips * ctx.peaks.flops)
