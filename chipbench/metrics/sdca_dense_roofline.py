"""The dense SDCA kernel's share of its roofline
(`kernels/sdca_bucket.py`): the required work of its epochs in the
traced window over its device time.  Silent where no such kernel ran."""


def read(ctx):
    return ctx["work"].kernel_roofline(ctx, "sdca_bucket_kernel")
