"""Per cent of the chip's published bf16 peak that the operations a
generation needs (``work/<model>.py``) reach at the window's generation
rate.  fp32 products at Precision.HIGHEST take several bf16 passes, so
even a perfect program sits far below 100 here."""


def read(ctx):
    w, flops, peak = ctx.get('window'), ctx.get('flops_per_gen'), \
        ctx.get('peak')
    if not w or not flops or not peak or w['seconds'] <= 0:
        return None
    return 100.0 * flops * w['generations'] / w['seconds'] \
        / peak['bf16_flops_per_s']
