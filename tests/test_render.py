"""Structural render tests — the `helm template` snapshot analogue."""

import base64

import yaml

import pytest

from kvedge_tpu.config.values import ChartValues, DEFAULT_VALUES
from kvedge_tpu.render import render_all, to_yaml, to_multidoc_yaml
from kvedge_tpu.render import bootconfig
from kvedge_tpu.render.manifests import render_notes


def _decode(secret, key="userdata"):
    return base64.b64decode(secret["data"][key]).decode("utf-8")


def test_default_render_manifest_set():
    # Mirrors the reference's rendered set: VM, DataVolume, 2 Secrets,
    # Service (SURVEY.md §1 L2) — here Deployment, PVC, 2 Secrets, Service,
    # plus the helm-test hook Pod (an addition; the reference has no test
    # hooks, SURVEY.md §4).
    chart = render_all(DEFAULT_VALUES)
    assert set(chart.manifests) == {
        "jax-tpu-runtime.yaml",
        "jax-tpu-state-volume.yaml",
        "jax-tpu-runtime-config-secret.yaml",
        "jax-tpu-boot-config-secret.yaml",
        "jax-tpu-runtime-service.yaml",
        "jax-tpu-healthz-test.yaml",
    }


def test_ssh_gate_drops_service_and_test_hook():
    chart = render_all(
        DEFAULT_VALUES.replace(tpuRuntimeEnableExternalSsh=False)
    )
    assert "jax-tpu-runtime-service.yaml" not in chart.manifests
    # Without the Service there is no stable single-host DNS target for
    # the hook either.
    assert "jax-tpu-healthz-test.yaml" not in chart.manifests
    assert len(chart.manifests) == 4


def test_dead_template_excluded_by_default_and_collides_if_included():
    # Reference quirk carried: the alternative volume template renders the
    # SAME resource name and only the packaging exclusion prevents the
    # collision (.helmignore:23-24, SURVEY.md §2 #6).
    chart = render_all(DEFAULT_VALUES)
    assert "jax-tpu-state-volume-prepopulated.yaml" not in chart.manifests
    full = render_all(DEFAULT_VALUES, include_dead=True)
    live = full.manifests["jax-tpu-state-volume.yaml"]
    dead = full.manifests["jax-tpu-state-volume-prepopulated.yaml"]
    assert live["metadata"]["name"] == dead["metadata"]["name"]
    assert "dataSourceRef" in dead["spec"]


def test_config_secret_roundtrip():
    toml = '[runtime]\nname = "edge-b"\n'
    chart = render_all(DEFAULT_VALUES.replace(jaxRuntimeConfig=toml))
    secret = chart.manifests["jax-tpu-runtime-config-secret.yaml"]
    assert _decode(secret) == toml


def test_boot_config_document_contents():
    values = DEFAULT_VALUES.replace(publicSshKey="ssh-ed25519 KEY me@host")
    chart = render_all(values)
    doc = _decode(chart.manifests["jax-tpu-boot-config-secret.yaml"])
    assert doc.startswith(bootconfig.HEADER)
    assert "ssh-ed25519 KEY me@host" in doc
    assert bootconfig.CONFIG_SERIAL in doc
    # bootcmd locates the config volume by serial before runcmd applies it
    # (ordering mirrors _helper.tpl:61-74).
    assert doc.index("bootcmd:") < doc.index("runcmd:")
    parsed = yaml.safe_load(doc)
    assert parsed["hostname"] == bootconfig.RUNTIME_HOSTNAME
    assert len(parsed["runcmd"]) == 2


def test_deployment_wiring():
    chart = render_all(DEFAULT_VALUES)
    dep = chart.manifests["jax-tpu-runtime.yaml"]
    spec = dep["spec"]
    assert spec["replicas"] == 1
    assert spec["strategy"] == {"type": "Recreate"}
    pod = spec["template"]["spec"]
    # Volume refs resolve to rendered resources.
    names = {
        m["metadata"]["name"] for m in chart.manifests.values()
    }
    for vol in pod["volumes"]:
        if "secret" in vol:
            assert vol["secret"]["secretName"] in names
        if "persistentVolumeClaim" in vol:
            assert vol["persistentVolumeClaim"]["claimName"] in names
    # Service selector matches pod labels.
    svc = chart.manifests["jax-tpu-runtime-service.yaml"]
    selector = svc["spec"]["selector"]
    pod_labels = spec["template"]["metadata"]["labels"]
    assert selector.items() <= pod_labels.items()
    assert spec["selector"]["matchLabels"].items() <= pod_labels.items()
    # TPU node selector uses the accelerator value.
    assert (
        pod["nodeSelector"]["cloud.google.com/gke-tpu-accelerator"]
        == DEFAULT_VALUES.tpuAccelerator
    )
    # Config secret is mounted under the serial-tagged path the boot
    # document tells the bootstrap to search for.
    mounts = pod["containers"][0]["volumeMounts"]
    cfg_mount = next(m for m in mounts if m["name"] == "jaxconfigdisk")
    assert cfg_mount["mountPath"].endswith(bootconfig.CONFIG_SERIAL)


def test_disk_size_flows_to_pvc():
    chart = render_all(DEFAULT_VALUES.replace(tpuRuntimeDiskSize="32Gi"))
    pvc = chart.manifests["jax-tpu-state-volume.yaml"]
    assert pvc["spec"]["resources"]["requests"]["storage"] == "32Gi"


def test_notes_mention_resources():
    notes = render_notes(DEFAULT_VALUES)
    name = "kvedge-tpu"
    assert f"kubectl get deployment {name}-runtime" in notes
    assert f"{name}-runtime-ssh-service" in notes


def test_yaml_emission_stable_and_parseable():
    chart = render_all(DEFAULT_VALUES)
    stream = to_multidoc_yaml([doc for _, doc in chart.ordered()])
    parsed = list(yaml.safe_load_all(stream))
    assert len(parsed) == 6
    assert to_yaml(chart.manifests["jax-tpu-runtime.yaml"]) == to_yaml(
        chart.manifests["jax-tpu-runtime.yaml"]
    )


def test_invalid_values_rejected_at_render():
    with pytest.raises(ValueError):
        render_all(ChartValues(tpuRuntimeDiskSize="bogus"))


def test_ssh_key_yaml_safe():
    # Empty key must stay a string (not YAML null); tricky keys must not
    # corrupt the document structure.
    doc = _decode(
        render_all(DEFAULT_VALUES).manifests["jax-tpu-boot-config-secret.yaml"]
    )
    assert yaml.safe_load(doc)["ssh_authorized_keys"] == [""]
    tricky = 'ssh-ed25519 AAAA user: laptop #1'
    doc = _decode(
        render_all(DEFAULT_VALUES.replace(publicSshKey=tricky)).manifests[
            "jax-tpu-boot-config-secret.yaml"
        ]
    )
    assert yaml.safe_load(doc)["ssh_authorized_keys"] == [tricky]


def test_status_port_follows_runtime_config():
    toml = "[status]\nport = 9000\n"
    chart = render_all(DEFAULT_VALUES.replace(jaxRuntimeConfig=toml))
    dep = chart.manifests["jax-tpu-runtime.yaml"]
    ports = dep["spec"]["template"]["spec"]["containers"][0]["ports"]
    assert {"containerPort": 9000, "name": "status"} in ports
    svc = chart.manifests["jax-tpu-runtime-service.yaml"]
    status = next(p for p in svc["spec"]["ports"] if p["name"] == "status")
    assert status["port"] == 9000 and status["targetPort"] == 9000


def test_bad_runtime_config_fails_at_render():
    # Install-time validation: the reference only surfaced a bad config.toml
    # inside the booted VM; here it fails the render/install command.
    with pytest.raises(ValueError):
        render_all(DEFAULT_VALUES.replace(jaxRuntimeConfig="not [valid"))


def test_ephemeral_status_port_rejected_at_render():
    with pytest.raises(ValueError, match="port 0"):
        render_all(DEFAULT_VALUES.replace(jaxRuntimeConfig="[status]\nport = 0\n"))


def test_probes_use_version_not_healthz():
    # Degraded runtimes must stay reachable: probes may only target the
    # unconditional /version route, never /healthz (503 when degraded).
    dep = render_all(DEFAULT_VALUES).manifests["jax-tpu-runtime.yaml"]
    container = dep["spec"]["template"]["spec"]["containers"][0]
    for probe in ("livenessProbe", "readinessProbe"):
        assert container[probe]["httpGet"]["path"] == "/version"
        assert container[probe]["httpGet"]["port"] == "status"


MULTIHOST_TOML = "[distributed]\nnum_processes = 4\n"
def test_healthz_test_hook_targets_service_dns():
    chart = render_all(DEFAULT_VALUES)
    pod = chart.manifests["jax-tpu-healthz-test.yaml"]
    assert pod["metadata"]["annotations"]["helm.sh/hook"] == "test"
    command = pod["spec"]["containers"][0]["command"]
    assert "http://kvedge-tpu-runtime-ssh-service:8476/healthz" in command
    assert pod["spec"]["restartPolicy"] == "Never"


def test_healthz_test_hook_honors_custom_status_port():
    chart = render_all(
        DEFAULT_VALUES.replace(jaxRuntimeConfig="[status]\nport = 9000\n")
    )
    command = chart.manifests["jax-tpu-healthz-test.yaml"][
        "spec"]["containers"][0]["command"]
    assert "http://kvedge-tpu-runtime-ssh-service:9000/healthz" in command


MULTIHOST = DEFAULT_VALUES.replace(tpuNumHosts=4, jaxRuntimeConfig=MULTIHOST_TOML)


def test_multihost_render_swaps_workload_and_adds_hosts_service():
    chart = render_all(MULTIHOST)
    assert set(chart.manifests) == {
        "jax-tpu-runtime-multihost.yaml",
        "jax-tpu-hosts-service.yaml",
        "jax-tpu-runtime-config-secret.yaml",
        "jax-tpu-boot-config-secret.yaml",
        "jax-tpu-runtime-service.yaml",
        "jax-tpu-healthz-test-multihost.yaml",
    }
    sts = chart.manifests["jax-tpu-runtime-multihost.yaml"]
    assert sts["kind"] == "StatefulSet"
    spec = sts["spec"]
    assert spec["replicas"] == 4
    assert spec["podManagementPolicy"] == "Parallel"
    assert spec["serviceName"] == "kvedge-tpu-runtime-hosts"
    pod = spec["template"]["spec"]
    # StatefulSet pod hostnames carry the ordinal the runtime infers its
    # process id from — a hostname override would erase that identity.
    assert "hostname" not in pod
    env = {e["name"]: e["value"] for e in pod["containers"][0]["env"]}
    assert env["KVEDGE_COORDINATOR"] == (
        "kvedge-tpu-runtime-0.kvedge-tpu-runtime-hosts"
    )
    # State is per-host claims, not one shared RWO volume.
    assert [v["name"] for v in pod["volumes"]] == [
        "jaxconfigdisk", "bootconfigdisk",
    ]
    claims = spec["volumeClaimTemplates"]
    assert claims[0]["metadata"]["name"] == "statedisk"
    assert claims[0]["spec"]["resources"]["requests"]["storage"] == "4Gi"


def test_multihost_hosts_service_is_headless_and_unready_tolerant():
    chart = render_all(MULTIHOST)
    svc = chart.manifests["jax-tpu-hosts-service.yaml"]
    assert svc["spec"]["clusterIP"] == "None"
    assert svc["spec"]["publishNotReadyAddresses"] is True
    assert svc["spec"]["ports"][0]["port"] == 8478


def test_multihost_coordinator_port_follows_config():
    toml = "[distributed]\nnum_processes = 2\ncoordinator_port = 9100\n"
    chart = render_all(DEFAULT_VALUES.replace(
        tpuNumHosts=2, jaxRuntimeConfig=toml
    ))
    svc = chart.manifests["jax-tpu-hosts-service.yaml"]
    assert svc["spec"]["ports"][0]["port"] == 9100


def test_multihost_topology_mismatch_fails_at_render():
    # Chart shape and TOML process group must agree, both ways.
    with pytest.raises(ValueError, match="num_processes"):
        render_all(DEFAULT_VALUES.replace(
            tpuNumHosts=4, jaxRuntimeConfig="[distributed]\nnum_processes = 2\n"
        ))
    with pytest.raises(ValueError, match="num_processes"):
        render_all(DEFAULT_VALUES.replace(tpuNumHosts=4))  # config says 1
    with pytest.raises(ValueError, match="tpuNumHosts"):
        render_all(DEFAULT_VALUES.replace(jaxRuntimeConfig=MULTIHOST_TOML))


def test_multihost_notes_name_statefulset():
    notes = render_notes(MULTIHOST)
    assert "kubectl get statefulset kvedge-tpu-runtime" in notes
    assert "deployment" not in notes


def test_multihost_pods_receive_expected_processes_env():
    chart = render_all(MULTIHOST)
    sts = chart.manifests["jax-tpu-runtime-multihost.yaml"]
    env = {e["name"]: e["value"]
           for e in sts["spec"]["template"]["spec"]["containers"][0]["env"]}
    assert env["KVEDGE_EXPECTED_PROCESSES"] == "4"


def test_singlehost_pod_receives_expected_processes_env():
    """The single-host Deployment states its topology too: without it, a
    helm install of a multi-process TOML with the default tpuNumHosts=1
    would pass both enforcement paths and the lone pod would block forever
    in jax.distributed.initialize waiting for peers."""
    chart = render_all(DEFAULT_VALUES)
    dep = chart.manifests["jax-tpu-runtime.yaml"]
    env = {e["name"]: e["value"]
           for e in dep["spec"]["template"]["spec"]["containers"][0]["env"]}
    assert env["KVEDGE_EXPECTED_PROCESSES"] == "1"


@pytest.mark.parametrize("values, manifest", [
    (DEFAULT_VALUES, "jax-tpu-runtime.yaml"),
    (MULTIHOST, "jax-tpu-runtime-multihost.yaml"),
])
def test_pods_keep_the_compile_cache_on_the_state_volume(values, manifest):
    """A rescheduled pod must not compile its programs again: the chart
    places JAX's persistent cache (JAX reads the variable itself) under
    the state volume's mount, not in the image's ephemeral layer."""
    container = render_all(values).manifests[manifest][
        "spec"]["template"]["spec"]["containers"][0]
    env = {e["name"]: e["value"] for e in container["env"]}
    mounts = [m["mountPath"] for m in container["volumeMounts"]
              if m["name"] == "statedisk"]
    assert mounts and env["JAX_COMPILATION_CACHE_DIR"].startswith(
        mounts[0] + "/")
