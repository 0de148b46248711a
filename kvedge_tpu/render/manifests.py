"""Build the chart's Kubernetes manifests as plain dicts.

Each builder mirrors one reference template (SURVEY.md §2 #4-#9); the
reference file is cited per function. The rendered set is:

==============================================  ================================
kvedge-tpu manifest                             reference template
==============================================  ================================
``jax-tpu-runtime.yaml`` (Deployment)           ``aziot-edge-vm.yaml`` (VM)
``jax-tpu-state-volume.yaml`` (PVC)             ``aziot-edge-data-volume-container.yaml``
``jax-tpu-state-volume-prepopulated.yaml``      ``aziot-edge-data-volume-disk.yaml``
  (dead alternative, excluded by .helmignore)     (dead alternative, excluded)
``jax-tpu-runtime-config-secret.yaml``          ``aziot-edge-runtime-config-secret.yaml``
``jax-tpu-boot-config-secret.yaml``             ``aziot-edge-vm-cloud-init-secret.yaml``
``jax-tpu-runtime-service.yaml`` (conditional)  ``aziot-edge-vm-service.yaml``
``jax-tpu-healthz-test.yaml`` /                 — (no reference analogue; the
``jax-tpu-healthz-test-multihost.yaml``           reference verifies by hand,
  (conditional ``helm test`` hook Pod)            its ``NOTES.txt:8-12``)
==============================================  ================================

With ``tpuNumHosts > 1`` the Deployment + PVC pair is replaced by
``jax-tpu-runtime-multihost.yaml`` (a StatefulSet with per-host claim
templates) plus ``jax-tpu-hosts-service.yaml`` (a headless service for
per-ordinal DNS) — no reference analogue (the reference is single-VM by
design, SURVEY.md §5); see :func:`runtime_statefulset`.

The KubeVirt VM becomes a ``Deployment`` with ``replicas: 1`` and
``strategy: Recreate`` holding a ReadWriteOnce state PVC: on node failure the
controller reschedules the pod and the PVC re-attaches — the same resilience
story (and the same node-bound-PVC caveat) as the reference's VM + DataVolume
(``README.md:88-89``). ``Recreate`` guarantees at most one pod holds the RWO
volume, as only one VM held the reference's boot disk.
"""

from __future__ import annotations

import base64
import dataclasses

from kvedge_tpu.config.runtime_config import RuntimeConfig
from kvedge_tpu.config.values import ChartValues
from kvedge_tpu.render import bootconfig
from kvedge_tpu.runtime.heartbeat import INIT_EVENTS_FILE
from kvedge_tpu.render.names import (
    DOMAIN_LABEL,
    OS_LABEL,
    common_labels,
    resource_name,
)
from kvedge_tpu.version import APP_VERSION, CHART_NAME

# The prebuilt runtime image (capability 5) — the containerDisk analogue of
# `docker://suneetnangia/ubuntu-container-disk:18.04`
# (aziot-edge-data-volume-container.yaml:12). Built by deployment/Dockerfile.
RUNTIME_IMAGE = f"kvedgedev/jax-tpu-runtime:{APP_VERSION}"

# GKE TPU node-selector key; the value comes from values.tpuAccelerator.
TPU_ACCELERATOR_SELECTOR = "cloud.google.com/gke-tpu-accelerator"

# Hardcoded pod resources, mirroring the reference's fixed VM size:
# 4 cores (aziot-edge-vm.yaml:18), 4096M (aziot-edge-vm.yaml:41), and the
# TPU chips of one host (the analogue of the VM owning its node's cores).
POD_CPU = "4"
POD_MEMORY = "4096M"
TPU_RESOURCE = "google.com/tpu"
TPU_CHIPS = 4

STATE_MOUNT = "/var/lib/kvedge/state"
# Native PID-1 supervisor (native/kvedge-init.cc): the in-container
# analogue of the systemd level that supervises the payload inside the
# reference VM, below the pod-restart level (the KubeVirt analogue).
# Its event log lives on the state volume so supervision history survives
# rescheduling; the status server surfaces it at /status. The filename is
# owned by the runtime module that reads it back.
INIT_BIN = "/opt/kvedge/bin/kvedge-init"
# JAX's persistent compile cache goes on the state volume, so a
# rescheduled pod does not compile its programs again. JAX reads the
# variable itself (runtime/compilecache.py sets no directory when it is
# set); unset, the cache would land in the image's own ephemeral layer.
COMPILE_CACHE_ENV = {
    "name": "JAX_COMPILATION_CACHE_DIR",
    "value": f"{STATE_MOUNT}/jax-cache",
}
INIT_EVENTS_PATH = f"{STATE_MOUNT}/{INIT_EVENTS_FILE}"
SSH_PORT = 22
# Default status port is owned by RuntimeConfig; the rendered containerPort /
# Service / NOTES follow the operator's [status] port when a runtime config
# is provided (see status_port()), so the two can't drift.
STATUS_PORT = RuntimeConfig.status_port


def parsed_runtime_config(values: ChartValues) -> RuntimeConfig:
    """The runtime config the opaque TOML value declares (defaults if empty).

    Parsing the opaque runtime config at render time also validates it — a
    failure mode the reference only surfaced inside the booted VM
    (`iotedge config apply` failing post-install, `_helper.tpl:74`) fails
    the install command instead.
    """
    if not values.jaxRuntimeConfig:
        return RuntimeConfig()
    return RuntimeConfig.parse(values.jaxRuntimeConfig)


def status_port(values: ChartValues) -> int:
    """The status port the manifests must expose."""
    port = parsed_runtime_config(values).status_port
    if port == 0:
        raise ValueError(
            "[status] port 0 (ephemeral) is only valid for local runs; "
            "manifests need a concrete port to expose"
        )
    return port


def _b64(text: str) -> str:
    return base64.b64encode(text.encode("utf-8")).decode("ascii")


def state_volume(values: ChartValues) -> dict:
    """State PVC — the DataVolume analogue.

    Reference: ``aziot-edge-data-volume-container.yaml`` — a CDI DataVolume
    importing a prebuilt boot disk into a ReadWriteOnce PVC sized by
    ``aziotEdgeVmDiskSize``. Pods boot from the OCI image instead of a disk,
    so the PVC holds only durable runtime *state* (heartbeats, checkpoints);
    it is dynamically provisioned from the cluster's default storage class.
    """
    name = resource_name(values.nameOverride)
    return {
        "apiVersion": "v1",
        "kind": "PersistentVolumeClaim",
        "metadata": {
            "name": f"{name}-runtime-dv",
            "labels": common_labels(),
        },
        "spec": {
            "accessModes": ["ReadWriteOnce"],
            "resources": {"requests": {"storage": values.tpuRuntimeDiskSize}},
        },
    }


def state_volume_prepopulated(values: ChartValues) -> dict:
    """Dead alternative to :func:`state_volume` — excluded from packaging.

    Reference: ``aziot-edge-data-volume-disk.yaml`` renders a DataVolume with
    the *same name* sourced over HTTP, and is excluded by ``.helmignore:24``
    ("takes ~30 mins to import"); only the ignore file prevents a name
    collision (SURVEY.md §2 #6). The analogue here: a PVC of the same name
    prepopulated from a volume snapshot, likewise excluded by
    ``deployment/helm/.helmignore`` and by :func:`render_all`.
    """
    doc = state_volume(values)
    doc["spec"]["dataSourceRef"] = {
        "apiGroup": "snapshot.storage.k8s.io",
        "kind": "VolumeSnapshot",
        "name": "jax-tpu-runtime-state-seed",
    }
    return doc


def runtime_config_secret(values: ChartValues) -> dict:
    """Opaque runtime-config Secret.

    Reference: ``aziot-edge-runtime-config-secret.yaml`` — the user's
    config.toml base64'd under the key ``userdata``.
    """
    name = resource_name(values.nameOverride)
    return {
        "apiVersion": "v1",
        "kind": "Secret",
        "metadata": {"name": f"{name}-runtime-jaxconfig"},
        "data": {"userdata": _b64(values.jaxRuntimeConfig)},
    }


def boot_config_secret(values: ChartValues) -> dict:
    """Boot-config Secret — the cloud-init Secret analogue.

    Reference: ``aziot-edge-vm-cloud-init-secret.yaml``. The reference names
    this Secret with raw ``.Values.nameOverride`` (its :4; latent mismatch
    noted at ``aziot-edge-vm.yaml:57``); kvedge-tpu uses the name helper —
    see the divergence note in :mod:`kvedge_tpu.render.names`.
    """
    name = resource_name(values.nameOverride)
    return {
        "apiVersion": "v1",
        "kind": "Secret",
        "metadata": {"name": f"{name}-runtime-bootconfig"},
        "data": {"userdata": _b64(bootconfig.boot_config_document(values))},
    }


def runtime_deployment(values: ChartValues) -> dict:
    """The core resource: the JAX runtime Deployment — the VM analogue.

    Reference: ``aziot-edge-vm.yaml``. Correspondences:

    * ``running: true`` (:9) -> ``replicas: 1`` + ``strategy: Recreate``;
    * 4 cores / q35 / 4096M (:18,:37,:41) -> cpu 4 / memory 4096M requests
      plus this host's TPU chips;
    * bootdisk -> DataVolume (:46-48) -> the state PVC mount;
    * serial-tagged config disk -> Secret (:25-28,:49-51) -> the config
      Secret mounted under ``/mnt/disks/<serial>``;
    * cloudInitNoCloud cdrom (:29-31,:52-57) -> the boot-config Secret
      mounted at ``/mnt/boot-secret``, consumed by the entrypoint;
    * masquerade NIC + static MAC (:32-35) -> TPU-accelerator node selector
      (the stable hardware identity) + pod networking;
    * ``kubevirt.io/domain`` label (:14) -> ``kvedge.dev/domain``.
    """
    name = resource_name(values.nameOverride)
    port = status_port(values)
    pod_labels = dict(common_labels())
    pod_labels[DOMAIN_LABEL] = f"{name}-runtime"
    return {
        "apiVersion": "apps/v1",
        "kind": "Deployment",
        "metadata": {
            "labels": {OS_LABEL: "linux"},
            "name": f"{name}-runtime",
        },
        "spec": {
            "replicas": 1,
            "strategy": {"type": "Recreate"},
            "selector": {"matchLabels": {DOMAIN_LABEL: f"{name}-runtime"}},
            "template": {
                "metadata": {"labels": pod_labels},
                "spec": {
                    "hostname": bootconfig.RUNTIME_HOSTNAME,
                    "nodeSelector": {
                        TPU_ACCELERATOR_SELECTOR: values.tpuAccelerator
                    },
                    "containers": [
                        {
                            "name": "runtime",
                            "image": RUNTIME_IMAGE,
                            "command": [
                                INIT_BIN,
                                "--events",
                                INIT_EVENTS_PATH,
                                "--",
                                "python",
                                "-m",
                                "kvedge_tpu.bootstrap.entrypoint",
                                "--boot-config",
                                f"{bootconfig.BOOT_SECRET_MOUNT}/userdata",
                            ],
                            "ports": [
                                {"containerPort": SSH_PORT, "name": "ssh"},
                                {"containerPort": port, "name": "status"},
                            ],
                            # Single-host topology, re-stated to the
                            # runtime so boot refuses a TOML declaring
                            # [distributed] num_processes > 1 (the lone
                            # pod would otherwise block forever in
                            # jax.distributed.initialize waiting for
                            # peers). The StatefulSet variant overwrites
                            # this with its replica count.
                            "env": [
                                {
                                    "name": "KVEDGE_EXPECTED_PROCESSES",
                                    "value": "1",
                                },
                                COMPILE_CACHE_ENV,
                            ],
                            "resources": {
                                "requests": {
                                    "cpu": POD_CPU,
                                    "memory": POD_MEMORY,
                                },
                                "limits": {TPU_RESOURCE: TPU_CHIPS},
                            },
                            # Probes target /version (server-alive), NOT
                            # /healthz: a degraded runtime must stay
                            # reachable for debugging (the analogue of
                            # ssh-ing into a VM whose payload failed), so
                            # kubelet must neither kill it nor pull it from
                            # the service endpoints. /healthz (503 when
                            # degraded) is for external monitors.
                            "livenessProbe": {
                                "httpGet": {
                                    "path": "/version",
                                    "port": "status",
                                },
                                # First XLA compile on a cold pod is slow.
                                "initialDelaySeconds": 120,
                                "periodSeconds": 10,
                            },
                            "readinessProbe": {
                                "httpGet": {
                                    "path": "/version",
                                    "port": "status",
                                },
                                "initialDelaySeconds": 5,
                                "periodSeconds": 10,
                            },
                            "volumeMounts": [
                                {
                                    "name": "statedisk",
                                    "mountPath": STATE_MOUNT,
                                },
                                {
                                    "name": "jaxconfigdisk",
                                    "mountPath": (
                                        f"{bootconfig.DISKS_ROOT}/"
                                        f"{bootconfig.CONFIG_SERIAL}"
                                    ),
                                    "readOnly": True,
                                },
                                {
                                    "name": "bootconfigdisk",
                                    "mountPath": bootconfig.BOOT_SECRET_MOUNT,
                                    "readOnly": True,
                                },
                            ],
                        }
                    ],
                    "volumes": [
                        {
                            "name": "statedisk",
                            "persistentVolumeClaim": {
                                "claimName": f"{name}-runtime-dv"
                            },
                        },
                        {
                            "name": "jaxconfigdisk",
                            "secret": {
                                "secretName": f"{name}-runtime-jaxconfig"
                            },
                        },
                        {
                            "name": "bootconfigdisk",
                            "secret": {
                                "secretName": f"{name}-runtime-bootconfig"
                            },
                        },
                    ],
                },
            },
        },
    }


def hosts_service(values: ChartValues) -> dict:
    """Headless Service giving multi-host pods stable per-ordinal DNS.

    No reference analogue exists (the reference is explicitly single-VM,
    SURVEY.md §5): this exists so StatefulSet pod N is reachable at
    ``<name>-runtime-N.<name>-runtime-hosts`` before readiness — the
    coordinator (pod 0) must be resolvable while every pod is still
    blocked joining the JAX cluster, hence
    ``publishNotReadyAddresses: true``. The advertised port follows the
    config's ``[distributed] coordinator_port`` (like :func:`status_port`,
    a custom port requires the Python renderer; the Helm chart pins the
    default).
    """
    name = resource_name(values.nameOverride)
    coordinator_port = parsed_runtime_config(values).distributed.coordinator_port
    return {
        "apiVersion": "v1",
        "kind": "Service",
        "metadata": {
            "labels": common_labels(),
            "name": f"{name}-runtime-hosts",
        },
        "spec": {
            "clusterIP": "None",
            "publishNotReadyAddresses": True,
            "selector": {DOMAIN_LABEL: f"{name}-runtime"},
            "ports": [
                {
                    "name": "coordinator",
                    "protocol": "TCP",
                    "port": coordinator_port,
                }
            ],
        },
    }


def runtime_statefulset(values: ChartValues) -> dict:
    """Multi-host variant of the runtime: one pod per slice host.

    Same pod template as :func:`runtime_deployment` with the multi-host
    deltas:

    * ``kind: StatefulSet`` with ``replicas = tpuNumHosts`` and
      ``podManagementPolicy: Parallel`` — ``jax.distributed.initialize``
      blocks until *all* processes join, so pods must start together
      (ordered startup would deadlock at pod 0);
    * no ``hostname:`` override — StatefulSet pod hostnames are
      ``<name>-runtime-<ordinal>``, which is exactly the identity
      :mod:`kvedge_tpu.parallel.distributed` infers the process id from;
    * ``KVEDGE_COORDINATOR`` env pointing at pod 0's stable headless-DNS
      name (no port: the runtime appends ``[distributed]
      coordinator_port``, so a custom port needs no re-render);
    * per-host state PVCs via ``volumeClaimTemplates`` (a ReadWriteOnce
      volume cannot span hosts). Heartbeats/boot counts are per-host;
      multi-host *checkpoints* should point ``state_dir`` at shared
      storage instead — the same honesty the reference applies to its
      node-bound PVC (``README.md:88-89``).
    """
    name = resource_name(values.nameOverride)
    doc = runtime_deployment(values)
    doc["kind"] = "StatefulSet"
    spec = doc["spec"]
    spec["replicas"] = values.tpuNumHosts
    del spec["strategy"]  # Recreate is a Deployment concept; RWO
    # exclusivity is per-ordinal here (each pod owns its own claim).
    spec["serviceName"] = f"{name}-runtime-hosts"
    spec["podManagementPolicy"] = "Parallel"
    pod = spec["template"]["spec"]
    del pod["hostname"]
    pod["containers"][0]["env"] = [
        {
            "name": "KVEDGE_COORDINATOR",
            "value": f"{name}-runtime-0.{name}-runtime-hosts",
        },
        # The chart's topology, re-stated to the runtime so boot can refuse
        # a TOML that silently disagrees (most dangerous case: a config
        # with no [distributed] section at all would otherwise boot N
        # healthy, independent single-host runtimes). Plain Helm cannot
        # parse the TOML at install time, so this boot-time cross-check is
        # the enforcement path for helm users.
        {
            "name": "KVEDGE_EXPECTED_PROCESSES",
            "value": str(values.tpuNumHosts),
        },
        COMPILE_CACHE_ENV,
    ]
    pod["volumes"] = [v for v in pod["volumes"] if v["name"] != "statedisk"]
    spec["volumeClaimTemplates"] = [
        {
            "metadata": {"name": "statedisk"},
            "spec": {
                "accessModes": ["ReadWriteOnce"],
                "resources": {
                    "requests": {"storage": values.tpuRuntimeDiskSize}
                },
            },
        }
    ]
    return doc


def _check_multihost_consistency(values: ChartValues) -> None:
    """Fail the render when the chart shape and the TOML topology disagree.

    The runtime would discover the mismatch only at boot (pods blocking in
    ``jax.distributed.initialize`` or joining a cluster smaller than the
    slice); the install-time failure is the same fast-fail divergence as
    config validation (README "Deliberate divergences" #2).
    """
    config_procs = parsed_runtime_config(values).distributed.num_processes
    if values.tpuNumHosts > 1 and config_procs != values.tpuNumHosts:
        raise ValueError(
            f"tpuNumHosts={values.tpuNumHosts} but the runtime config "
            f"declares [distributed] num_processes={config_procs}; the "
            "StatefulSet replica count and the JAX process group must "
            "match (set num_processes in the config TOML)"
        )
    if values.tpuNumHosts == 1 and config_procs > 1:
        raise ValueError(
            f"runtime config declares [distributed] num_processes="
            f"{config_procs} but tpuNumHosts=1; set "
            f"--set tpuNumHosts={config_procs} to render the multi-host "
            "StatefulSet"
        )


def access_service(values: ChartValues) -> dict | None:
    """Conditional LoadBalancer for external SSH + status access.

    Reference: ``aziot-edge-vm-service.yaml`` — rendered only when the
    enable flag is true (:1), LoadBalancer on TCP 22 (:13-17), selecting the
    runtime pod by domain label (:10-11), ``externalTrafficPolicy: Cluster``
    (:9). kvedge-tpu adds the status port alongside SSH.
    """
    if not values.tpuRuntimeEnableExternalSsh:
        return None
    name = resource_name(values.nameOverride)
    port = status_port(values)
    return {
        "apiVersion": "v1",
        "kind": "Service",
        "metadata": {
            "labels": common_labels(),
            "name": f"{name}-runtime-ssh-service",
        },
        "spec": {
            "externalTrafficPolicy": "Cluster",
            "selector": {DOMAIN_LABEL: f"{name}-runtime"},
            "ports": [
                {
                    "name": "ssh",
                    "protocol": "TCP",
                    "port": SSH_PORT,
                    "targetPort": SSH_PORT,
                },
                {
                    "name": "status",
                    "protocol": "TCP",
                    "port": port,
                    "targetPort": port,
                },
            ],
            "type": "LoadBalancer",
        },
    }


def healthz_test_pod(values: ChartValues) -> dict | None:
    """``helm test`` hook Pod: polls the runtime's /healthz in-cluster.

    The reference's post-install verification is manual (``kubectl get
    vmi`` + ssh, reference ``NOTES.txt:8-12``; no helm test hooks exist —
    SURVEY.md §4). This hook automates it: ``helm test <release>`` runs
    the runtime image's :mod:`kvedge_tpu.runtime.healthcheck` against the
    runtime's stable in-cluster DNS name — the multi-host headless
    per-pod name when ``tpuNumHosts > 1``, the access Service otherwise.
    A single-host install with the access Service disabled has no stable
    DNS target, so no hook renders (``helm test`` then reports no tests,
    matching the reference's "verify by hand" posture).
    """
    name = resource_name(values.nameOverride)
    port = status_port(values)
    if values.tpuNumHosts > 1:
        host = f"{name}-runtime-0.{name}-runtime-hosts"
    elif values.tpuRuntimeEnableExternalSsh:
        host = f"{name}-runtime-ssh-service"
    else:
        return None
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {
            "labels": common_labels(),
            "annotations": {
                "helm.sh/hook": "test",
                "helm.sh/hook-delete-policy":
                    "before-hook-creation,hook-succeeded",
            },
            "name": f"{name}-runtime-healthz-test",
        },
        "spec": {
            "restartPolicy": "Never",
            "containers": [
                {
                    "name": "healthz",
                    "image": RUNTIME_IMAGE,
                    "command": [
                        "python",
                        "-m",
                        "kvedge_tpu.runtime.healthcheck",
                        f"http://{host}:{port}/healthz",
                        "--deadline",
                        "240",
                    ],
                }
            ],
        },
    }


@dataclasses.dataclass(frozen=True)
class RenderedChart:
    """The rendered manifest set, keyed by output filename."""

    manifests: dict[str, dict]
    notes: str

    def ordered(self) -> list[tuple[str, dict]]:
        return sorted(self.manifests.items())


def render_notes(values: ChartValues) -> str:
    """Post-install usage text (reference: ``templates/NOTES.txt``)."""
    name = resource_name(values.nameOverride)
    workload = "deployment" if values.tpuNumHosts == 1 else "statefulset"
    return (
        f"You have installed release {APP_VERSION} of {CHART_NAME}.\n"
        "\n"
        "To check the status of the newly created JAX TPU runtime, try:\n"
        f"kubectl get {workload} {name}-runtime\n"
        "\n"
        "To query the runtime status endpoint (once the pod is running):\n"
        f"curl http://$(kubectl get service {name}-runtime-ssh-service "
        "--output jsonpath='{.status.loadBalancer.ingress[0].ip}')"
        f":{status_port(values)}/status\n"
        "\n"
        "To connect to the runtime pod over SSH:\n"
        f"ssh kvedge@$(kubectl get service {name}-runtime-ssh-service "
        "--output jsonpath='{.status.loadBalancer.ingress[0].ip}')\n"
    ) + (
        "\n"
        "To verify the runtime from inside the cluster:\n"
        "helm test <release-name>\n"
        if healthz_test_pod(values) is not None else ""
    )


def render_all(values: ChartValues, include_dead: bool = False) -> RenderedChart:
    """Render the full manifest set.

    ``include_dead=False`` mirrors the packaging exclusion of the
    prepopulated-volume alternative (reference ``.helmignore:23-24``): the
    dead template exists in the chart source but is never rendered; if it
    were, its name would collide with the live state volume.
    """
    values.validate()
    _check_multihost_consistency(values)
    manifests: dict[str, dict] = {
        "jax-tpu-runtime-config-secret.yaml": runtime_config_secret(values),
        "jax-tpu-boot-config-secret.yaml": boot_config_secret(values),
    }
    if values.tpuNumHosts == 1:
        manifests["jax-tpu-runtime.yaml"] = runtime_deployment(values)
        manifests["jax-tpu-state-volume.yaml"] = state_volume(values)
    else:
        manifests["jax-tpu-runtime-multihost.yaml"] = (
            runtime_statefulset(values)
        )
        manifests["jax-tpu-hosts-service.yaml"] = hosts_service(values)
    if include_dead:
        manifests["jax-tpu-state-volume-prepopulated.yaml"] = (
            state_volume_prepopulated(values)
        )
    service = access_service(values)
    if service is not None:
        manifests["jax-tpu-runtime-service.yaml"] = service
    test_pod = healthz_test_pod(values)
    if test_pod is not None:
        key = ("jax-tpu-healthz-test.yaml" if values.tpuNumHosts == 1
               else "jax-tpu-healthz-test-multihost.yaml")
        manifests[key] = test_pod
    return RenderedChart(manifests=manifests, notes=render_notes(values))
