"""The benchmark's own code: traffic, arithmetic, reference, reduction.

Nothing here imports the program except ``serve.py`` and ``train.py``,
which build the system under test and drive it."""
