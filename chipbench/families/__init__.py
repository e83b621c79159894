"""One loader per model family, found by the ``family`` key of a
configuration file: model + parameters on the device from the seed."""
