"""Plain float32 references that decide a benchmark run's ``correct``.

A configuration names its module here (``bench/spec.py`` states what one
holds; ``lm`` where it names none); ``placement.py`` spreads a reference
over a cell's chips. Nothing here imports the program under test
(``repro``)."""
