"""One module per model family: how the benchmark builds the program's
model from a configuration file, and the family's seeded inputs. Found by
the ``family`` key of a configuration; the plain reference of the same
name is in ``reference/``."""
