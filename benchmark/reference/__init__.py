"""Plain references the system is compared with.  Nothing in this
package imports ``horovod_tpu``."""
