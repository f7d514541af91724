"""The plain float32 reference: one architecture module per block family
(``<name>.py``, named by a configuration's ``"reference"`` key and loaded
by :func:`harness.registry.reference`), and what they share
(:mod:`reference.common`)."""
