"""The dense decoder family: what the harness needs of a family, found
by the configuration's `family` key (`families/<family>.py`)."""
from chipbench.flops.dense import train_step  # noqa: F401
from chipbench.models import program_kwargs  # noqa: F401
from chipbench.reference.dense import Reference  # noqa: F401
from chipbench.weights import canonical_specs, make  # noqa: F401
