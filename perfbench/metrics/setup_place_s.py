"""setup_place_s (s): what the program's own set-up stages before the
window cover — ``trainer.place`` + ``trainer.build``, or ``module.bind``
+ ``init_params`` + ``init_optimizer`` + ``set_params`` — less what
compiled inside them (``setup_reduce.py``).  Host time of the stages:
``device_put`` and the copies they dispatch are asynchronous, and what
the device still owes when a stage returns is paid under the first
steps, outside this number.  Moves ``setup_s``."""
import setup_reduce


def read(ctx):
    return setup_reduce.place_s(ctx)
