"""gqa_decode_roofline: the GQA paged-decode Pallas kernel as a share of
its roofline.

Kernel time: device-trace operations named ``KERNEL``.N (the name the
TPU trace gives the kernel's custom call), inside the decode program.
Work per decoded row and layer: 4*H*d*ctx FLOPs;
the live K and V (ctx*KVH*d bf16 each), query and output in bytes
(``bench.harness.work.gqa_decode``)."""
from bench.harness import work
from bench.harness.readings import decode_roofline
from bench.harness.reduce import op_name

KERNEL = "_gqa_jit"


def read(run):
    return decode_roofline(run, lambda name: op_name(name).startswith(KERNEL),
                           work.gqa_decode)
