"""Stand-ins for ``compiler/encode.py`` ``encode_worker`` that an encoder
worker process can import by name (``tests/test_encode_blocks.py`` hands
them to the pool in its place).  They import no jax."""

import errno
import os


def die_holding_a_block(args):
    """A worker that got as far as creating the block its task's offer
    forces (an offer of no block, or of one too small), and then died
    with the chunk: the process is gone, its task never resolves."""
    from kyverno_tpu.compiler.encode import open_block
    _docs, _contexts, _padded_n, offer = args
    open_block(offer, offer[1] + (1 << 16))
    os._exit(1)


def no_block_to_be_had(args):
    """A worker on a host whose ``/dev/shm`` has no room."""
    raise OSError(errno.ENOSPC, 'no room for a block')
