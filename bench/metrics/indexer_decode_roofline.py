"""indexer_decode_roofline: the DSA lightning-indexer Pallas kernel in
decode, as a share of its roofline.

Kernel time: device-trace operations named ``KERNEL``.N (the name the
TPU trace gives the kernel's custom call), inside the decode program.
Work per decoded row and layer: 2*Hi*Di*ctx FLOPs;
the ctx cached index keys, the query and the head weights in bytes
(``bench.harness.work.indexer_decode``)."""
from bench.harness import work
from bench.harness.readings import decode_roofline
from bench.harness.reduce import op_name

KERNEL = "_indexer_jit"


def read(run):
    return decode_roofline(run, lambda name: op_name(name).startswith(KERNEL),
                           work.indexer_decode)
