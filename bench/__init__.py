"""The chip benchmark: ``python3 bench/run.py`` (see ``bench/README.md``)."""
