"""Launchers: the RELMAS training and serving drivers and the compile
cache they share."""
