"""Device busy seconds per block outside the CD kernels (trace): capture, Sigma, recompute, emit."""

from lib import readers

read = readers.capture_device_s
